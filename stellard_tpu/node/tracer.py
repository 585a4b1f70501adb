"""Tracing plane: transaction-lifecycle spans in a bounded ring buffer.

The last two perf PRs (pipelined close, delta replay) each justified
themselves with hand-instrumented stage timers, and each subsystem grew
a private latency tracker. This module is the shared substrate those
timers collapse into: a Dapper-style causal trace (Sigelman et al.,
2010) threaded per TRANSACTION (trace id = txid) and per LEDGER (trace
id = "ledger-<seq>") through submit → verify batch → open apply /
speculation → consensus round → close splice/fallback → persist.

Design constraints, in order:

- the hot paths must not notice it: one short lock around a ring-slot
  write, no allocation before the enabled/sampling gates, and the
  subsystems that already measure intervals (JobQueue, VerifyPlane,
  ClosePipeline) hand their existing timestamps to ``complete()``
  instead of timing twice;
- bounded memory: a fixed ring of ``capacity`` records — wraparound
  overwrites the oldest, and ``dropped`` counts what scrolled away;
- deterministic sampling: the record/skip decision for a transaction is
  a pure function of (txid, sample rate), so every subsystem a tx
  passes through makes the SAME decision and a sampled tx always gets
  its whole tree. Ledger-scoped spans (a handful per close) are always
  recorded;
- three exports: Chrome trace-event JSON (``chrome_trace`` — loadable
  in Perfetto / chrome://tracing, served by the ``trace_dump`` admin
  RPC), span-derived per-stage latency histograms (``stage_hist``,
  pushed through CollectorManager hooks to statsd), and a compact
  recent consensus/close timeline for ``server_state``/``get_counts``.

Cross-thread spans use the explicit ``begin()``/``end()`` token pair
(the verify plane completes futures on its flusher thread; the close
pipeline persists on its drain worker). Same-thread nesting uses the
``span()`` context manager, which maintains a thread-local parent
stack so child spans link without any caller bookkeeping.

Cross-NODE propagation (``[trace] propagate``): overlay frames carry a
compact trace context — trace id + parent span id + sampled bit — in an
optional high-numbered wire extension (overlay/wire.py TraceContext).
Span ids are node-unique (a per-tracer 32-bit tag in the high bits), so
spans recorded on different nodes never collide and a merged dump
(tools/traceview.py --merge) resolves parent links across processes.
The deterministic per-txid sampling means every node makes the SAME
record/skip decision, so a sampled transaction's causal tree is
complete fleet-wide. ``wire_context()`` exports the sender side;
``adopt_context()`` registers the foreign parent on the receiver, and
any span recorded for that trace with no local parent links under it
(marked ``remote`` in the dump — a single-node validation must not
demand the foreign parent resolve locally).

One clock with the device trace: spans are stamped ``perf_counter``
minus ``epoch``, and the dump exports that epoch (``otherData.
epoch_ns``). Whoever starts a ``jax.profiler`` capture (the ``profile``
RPC) calls ``anchor()``, which writes ONE instant host annotation into
the XPlane whose name carries this tracer's ``node_tag`` and the
``perf_counter`` reading of that moment; a reader then places every
span of the ring, ``complete()``-style and cross-thread ones included,
on the profiler's clock with ``place_on_trace_clock`` (tools/
traceview.py --xplane draws both on one timeline). No annotation per
span: a span costs the same whether or not a capture is live.

The interpreter's collector is part of the runtime every span runs on:
``GC_PROBE`` (one ``gc.callbacks`` hook per process, installed by the
nodes and replay tools whose tracer is enabled) counts collections and
their pauses per generation and records the long ones as ``gc.collect``
spans in the rings of the tracers that installed it.

Who ran: the host is one interpreter with a dozen threads and one lock,
so a span's wall clock measures its neighbours too. A span opened with
``begin()``/``span()`` may also read the opening thread's CPU clock
(``time.thread_time()``); ``end()`` on the SAME thread exports the
difference as ``cpu_us``. A span ended on another thread carries none,
and neither does one that was not clocked: absence means "not known",
never 0. The clock is a system call (microseconds where the kernel is
sandboxed), so of a transaction's spans that a thread opens outside any
other, one in ``Tracer.CPU_EVERY`` is clocked, with everything opened
beneath it on that thread: a reader sums the clocked spans of a name and
scales by their share of all. Ledger- and subsystem-scoped spans (a
handful a close) are always clocked, and ``complete()`` takes ``cpu_s``
from callers that clock their own stages. ``THREAD_ROLES`` (one registry a
process) sums the CPU seconds of the node's own threads by role; the
ledger master puts a close CYCLE's differences on ``close.total``.
"""

from __future__ import annotations

import gc
import itertools
import os
import re
import threading
import time
import weakref
import zlib
from collections import deque
from typing import Optional

from .heapaging import HEAP_AGING
from .metrics import LatencyHist

__all__ = ["Tracer", "SpanToken", "get_tracer", "GC_PROBE", "THREAD_ROLES",
           "ROLES", "parse_anchor", "place_on_trace_clock"]

# the clock anchor's name in a profiler trace: node tag and the
# perf_counter reading (ns) taken as the annotation was written
ANCHOR_PREFIX = "stellard.anchor"
_ANCHOR = re.compile(
    r"^stellard\.anchor tag=([0-9a-f]{8}) pc_ns=(\d+)$")

# a collection at least this long is a span (a full collection always
# is: there are a handful a minute and each holds every thread)
GC_SPAN_MIN_S = 0.010

# bound on the per-trace foreign-parent / last-span maps the propagation
# plane keeps (FIFO eviction; a trace is a txid or "ledger-<seq>")
_CTX_CAP = 4096

# categories whose events feed the server_state consensus/close timeline
_TIMELINE_CATS = frozenset({"close", "consensus", "persist"})

# finer-than-default bounds for span stages: close/persist stages live
# in the 1-500 ms band where the default decade buckets are too coarse
STAGE_BOUNDS = (
    0.1, 0.25, 0.5, 1.0, 2.0, 3.0, 5.0, 8.0, 12.0, 20.0, 30.0, 50.0,
    80.0, 120.0, 200.0, 300.0, 500.0, 800.0, 1200.0, 2000.0, 5000.0,
)


class SpanToken:
    """Handle for an in-flight span; pass it across threads and hand it
    back to ``end()`` (or as ``parent=`` of a child span)."""

    __slots__ = ("name", "cat", "trace", "span_id", "parent", "t0",
                 "tid", "attrs", "c0", "cpu_us")

    def __init__(self, name, cat, trace, span_id, parent, t0, tid, attrs,
                 c0=None, cpu_us=None):
        self.name = name
        self.cat = cat
        self.trace = trace
        self.span_id = span_id
        self.parent = parent
        self.t0 = t0
        self.tid = tid
        self.attrs = attrs
        # the opening thread's CPU clock at begin(); cpu_us is what that
        # thread ran until end() (None: ended elsewhere, or never clocked)
        self.c0 = c0
        self.cpu_us = cpu_us


class _NullSpan:
    """Context manager returned when tracing is off / the tx unsampled."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *_exc):
        return False


_NULL_SPAN = _NullSpan()


class _SpanCM:
    """Context-manager wrapper that maintains the thread-local parent
    stack (so nested ``span()`` calls link parent→child) and ends the
    span on exit."""

    __slots__ = ("_tracer", "token")

    def __init__(self, tracer: "Tracer", token: SpanToken):
        self._tracer = tracer
        self.token = token

    def __enter__(self) -> SpanToken:
        stack = self._tracer._stack()
        stack.append(self.token)
        return self.token

    def __exit__(self, *_exc):
        stack = self._tracer._stack()
        if stack and stack[-1] is self.token:
            stack.pop()
        self._tracer.end(self.token)
        return False


def _trace_id(txid, seq) -> Optional[str]:
    """Normalize the two causal keys: a tx trace is the txid hex, a
    ledger trace is "ledger-<seq>"."""
    if txid is not None:
        return txid.hex() if isinstance(txid, (bytes, bytearray)) else str(txid)
    if seq is not None:
        return f"ledger-{seq}"
    return None


class Tracer:
    """Lock-light bounded ring-buffer span recorder."""

    # one of a transaction's spans in this many reads the thread's CPU
    # clock (``_clocked``)
    CPU_EVERY = 8

    def __init__(self, capacity: int = 16384, enabled: bool = True,
                 sample: float = 0.125, propagate: bool = False,
                 node_tag: Optional[int] = None):
        self.enabled = bool(enabled)
        self.capacity = max(16, int(capacity))
        self.sample = min(1.0, max(0.0, float(sample)))
        self.propagate = bool(propagate)
        # sampling threshold in basis points of 10000, precomputed so the
        # per-tx gate is one crc32 + one compare
        self._sample_bp = int(round(self.sample * 10000))
        self._lock = threading.Lock()
        self._ring: list = [None] * self.capacity
        self._n = 0  # total records ever pushed
        self._ids = itertools.count(1)
        # node-unique span-id prefix: spans from different tracers
        # (nodes / processes) occupy disjoint id ranges, so a merged
        # multi-node dump resolves cross-node parent links directly
        if node_tag is None:
            node_tag = int.from_bytes(os.urandom(4), "big") or 1
        self.node_tag = node_tag & 0xFFFFFFFF
        self._tag = self.node_tag << 32
        self._epoch = time.perf_counter()
        self._tls = threading.local()
        # span-derived per-stage latency histograms (name -> hist)
        self.stage_hist: dict[str, LatencyHist] = {}
        # propagation state: trace -> foreign parent span id (adopted
        # from the wire) and trace -> last locally recorded span id
        # (exported as the parent of outbound frames). Bounded FIFO.
        self._foreign: dict[str, int] = {}
        self._last: dict[str, int] = {}
        # optional flight-recorder feed (node/health.py FlightRecorder):
        # every recorded span/instant also lands in its black box
        self.flight = None
        # spans handed over by a caller that must not take _lock (the
        # collector's hook, see complete_unlocked); they move into the
        # ring under the next lock hold
        self._parked: deque = deque()

    @classmethod
    def from_config(cls, cfg) -> "Tracer":
        """Build from a node Config's [trace] knobs."""
        return cls(
            capacity=cfg.trace_capacity,
            enabled=cfg.trace_enabled,
            sample=cfg.trace_sample,
            propagate=getattr(cfg, "trace_propagate", False),
        )

    # -- one clock with the device trace ---------------------------------

    @property
    def epoch(self) -> float:
        """The ``perf_counter`` reading every ``ts`` of this ring is
        relative to (seconds)."""
        return self._epoch

    def anchor(self) -> Optional[int]:
        """Write the clock anchor into a LIVE ``jax.profiler`` capture:
        an instant host annotation named ``stellard.anchor tag=<node
        tag> pc_ns=<perf_counter now>``, and the same reading as a
        ``trace.anchor`` instant in the ring. Called by the door that
        started the capture (the ``profile`` RPC), once at its start
        and once before its stop (two anchors bound the drift between
        the two clocks). -> the reading in ns, or None when the tracer
        is disabled (nothing is written then)."""
        if not self.enabled:
            return None
        from jax.profiler import TraceAnnotation

        pc_ns = int(time.perf_counter() * 1e9)
        with TraceAnnotation(
                f"{ANCHOR_PREFIX} tag={self.node_tag:08x} pc_ns={pc_ns}"):
            pass
        self.instant("trace.anchor", "trace", pc_ns=pc_ns)
        return pc_ns

    # -- sampling ----------------------------------------------------------

    def sampled(self, txid) -> bool:
        """Deterministic per-transaction record/skip decision: a pure
        function of (txid, rate) so every pipeline stage agrees and a
        sampled tx gets its complete span tree."""
        if not self.enabled:
            return False
        bp = self._sample_bp
        if bp >= 10000:
            return True
        if bp <= 0:
            return False
        key = txid if isinstance(txid, (bytes, bytearray)) else str(txid).encode()
        return (zlib.crc32(key) & 0xFFFFFFFF) % 10000 < bp

    def _admit(self, txid) -> bool:
        """Gate shared by every record path: enabled, and — when the
        event is tx-scoped — the tx is sampled. Ledger/subsystem-scoped
        events (txid None) are always admitted when enabled: there are
        only a handful per close."""
        if not self.enabled:
            return False
        if txid is None:
            return True
        return self.sampled(txid)

    # -- recording ---------------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _now_us(self) -> int:
        return int((time.perf_counter() - self._epoch) * 1e6)

    def _clocked(self, name: str, per_tx: bool = True) -> bool:
        """Whether the span the calling thread opens now reads its CPU
        clock. Beneath a ``span()`` it follows that span (a clocked
        span's same-thread children are all clocked, so that its self
        CPU can be told). Outside any: a ledger- or subsystem-scoped
        span always (a handful a close); a transaction's, or a reading
        ``thread_cpu(one_in_few=...)`` asks for, the thread's first of
        that name and then every ``CPU_EVERY``-th of it (counted by
        name: a thread that opens two names in turn clocks both)."""
        stack = self._stack()
        if stack:
            return stack[-1].c0 is not None
        if not per_tx:
            return True
        roots = getattr(self._tls, "roots", None)
        if roots is None:
            roots = self._tls.roots = {}
        n = roots.get(name, 0)
        roots[name] = n + 1
        return n % self.CPU_EVERY == 0

    def thread_cpu(self, one_in_few: Optional[str] = None
                   ) -> Optional[float]:
        """The calling thread's CPU clock, for a caller of ``complete()``
        to read beside its ``perf_counter()`` readings; None, and no
        clock read, with the tracer disabled. ``one_in_few``: the span's
        name, from a site that runs thousands of times a close
        (``cache.fault``): a reading only where a span of that name
        opened here would be clocked."""
        if not self.enabled or (
                one_in_few is not None and not self._clocked(one_in_few)):
            return None
        return time.thread_time()

    @staticmethod
    def cpu_since(c0: Optional[float],
                  c1: Optional[float] = None) -> Optional[float]:
        """The ``cpu_s`` a caller hands ``complete()``: from one
        ``thread_cpu()`` reading to a later one of the same thread (now,
        where none is given); None, and no clock read, where the first
        was not taken."""
        if c0 is None:
            return None
        return (time.thread_time() if c1 is None else c1) - c0

    def _push(self, rec: tuple) -> None:
        with self._lock:
            if self._parked:
                self._unpark_locked()
            self._ring[self._n % self.capacity] = rec
            self._n += 1

    def _parent_id(self, parent) -> Optional[int]:
        if parent is not None:
            return parent.span_id if isinstance(parent, SpanToken) else int(parent)
        stack = self._stack()
        return stack[-1].span_id if stack else None

    def _next_id(self) -> int:
        return self._tag | (next(self._ids) & 0xFFFFFFFF)

    def _resolve_parent(self, parent, trace, attrs):
        """Parent resolution order: explicit > thread-local stack >
        foreign parent adopted from the wire for this trace. A foreign
        parent marks the record ``remote`` so single-node validation
        knows the link resolves on another node's dump."""
        parent_id = self._parent_id(parent)
        if parent_id is None and trace is not None and self._foreign:
            parent_id = self._foreign.get(trace)
            if parent_id is not None:
                attrs = {**(attrs or {}), "remote": 1}
        return parent_id, attrs

    def begin(self, name: str, cat: str, txid=None, seq=None, parent=None,
              **attrs) -> Optional[SpanToken]:
        """Open a span; returns a token to ``end()`` (possibly from
        another thread), or None when tracing is off / the tx unsampled.
        Without an explicit ``parent``, the opening thread's innermost
        ``span()`` context is the parent."""
        if not self._admit(txid):
            return None
        trace = _trace_id(txid, seq)
        parent_id, attrs = self._resolve_parent(parent, trace, attrs)
        return SpanToken(
            name, cat, trace, self._next_id(),
            parent_id, time.perf_counter(),
            threading.get_ident(), attrs or None,
            time.thread_time() if self._clocked(name, txid is not None)
            else None,
        )

    def end(self, token: Optional[SpanToken], **attrs) -> None:
        """Close a span opened with ``begin()``. None tokens are
        accepted so callers never branch on the sampling decision."""
        if token is None:
            return
        t1 = time.perf_counter()
        if token.c0 is not None and threading.get_ident() == token.tid:
            token.cpu_us = int((time.thread_time() - token.c0) * 1e6)
        ms = (t1 - token.t0) * 1000.0
        if attrs:
            token.attrs = {**(token.attrs or {}), **attrs}
        self._record_complete(token, t1, ms)

    def span(self, name: str, cat: str, txid=None, seq=None, parent=None,
             **attrs):
        """``with tracer.span(...):`` — same-thread span with automatic
        parent linkage through the thread-local stack."""
        token = self.begin(name, cat, txid=txid, seq=seq, parent=parent,
                           **attrs)
        if token is None:
            return _NULL_SPAN
        return _SpanCM(self, token)

    def complete(self, name: str, cat: str, t0: float, t1: float,
                 txid=None, seq=None, parent=None, cpu_s=None,
                 **attrs) -> None:
        """Record an already-measured interval (perf_counter pair) as a
        span — the zero-extra-timing path for subsystems that already
        clock their stages (JobQueue, VerifyPlane, ClosePipeline).
        ``cpu_s``: what the recording thread ran inside the interval, a
        ``time.thread_time()`` difference taken where the caller takes
        its ``perf_counter()`` readings; exported as ``cpu_us``."""
        if not self._admit(txid):
            return
        trace = _trace_id(txid, seq)
        parent_id, attrs = self._resolve_parent(parent, trace, attrs)
        token = SpanToken(
            name, cat, trace, self._next_id(),
            parent_id, t0, threading.get_ident(),
            attrs or None,
            None, None if cpu_s is None else int(cpu_s * 1e6),
        )
        self._record_complete(token, t1, (t1 - t0) * 1000.0)

    def _record_complete(self, token: SpanToken, t1: float, ms: float) -> None:
        with self._lock:
            if self._parked:
                self._unpark_locked()
            self._write_locked(token, t1, ms)
        fl = self.flight
        if fl is not None:
            fl.note_span("X", token.name, token.cat, token.trace, ms)

    def _write_locked(self, token: SpanToken, t1: float, ms: float) -> None:
        hist = self.stage_hist.get(token.name)
        if hist is None:
            hist = self.stage_hist[token.name] = LatencyHist(
                bounds=STAGE_BOUNDS, interpolate=True
            )
        hist.record(ms)
        self._ring[self._n % self.capacity] = (
            "X", token.name, token.cat, token.trace, token.span_id,
            token.parent,
            int((token.t0 - self._epoch) * 1e6),
            max(0, int((t1 - token.t0) * 1e6)),
            token.tid, token.attrs, token.cpu_us,
        )
        self._n += 1
        if self.propagate and token.trace is not None:
            self._note_last_locked(token.trace, token.span_id)

    def complete_unlocked(self, name: str, cat: str, t0: float, t1: float,
                          **attrs) -> None:
        """``complete()`` for a caller that may be running INSIDE this
        tracer's lock: the collector's hook fires at any allocation, one
        made under ``_lock`` included, and the lock does not nest. The
        span is parked (a deque append takes no lock) and moves into the
        ring under the next lock hold, a record or a dump."""
        if not self.enabled:
            return
        parent_id, attrs = self._resolve_parent(None, None, attrs)
        token = SpanToken(name, cat, None, self._next_id(), parent_id, t0,
                          threading.get_ident(), attrs or None)
        self._parked.append((token, t1))
        fl = self.flight
        if fl is not None:
            fl.note_span("X", name, cat, None, (t1 - t0) * 1000.0)

    def _unpark_locked(self) -> None:
        while self._parked:
            token, t1 = self._parked.popleft()
            self._write_locked(token, t1, (t1 - token.t0) * 1000.0)

    def instant(self, name: str, cat: str, txid=None, seq=None, parent=None,
                **attrs) -> None:
        """Point event (consensus round events, splice/fallback marks)."""
        if not self._admit(txid):
            return
        trace = _trace_id(txid, seq)
        parent_id, attrs = self._resolve_parent(parent, trace, attrs)
        span_id = self._next_id()
        self._push((
            "i", name, cat, trace, span_id, parent_id,
            self._now_us(), 0, threading.get_ident(), attrs or None, None,
        ))
        if self.propagate and trace is not None:
            with self._lock:
                self._note_last_locked(trace, span_id)
        fl = self.flight
        if fl is not None:
            fl.note_span("i", name, cat, trace, 0.0)

    # -- cross-node propagation --------------------------------------------

    def _note_last_locked(self, trace: str, span_id: int) -> None:
        last = self._last
        if trace not in last and len(last) >= _CTX_CAP:
            last.pop(next(iter(last)))
        last[trace] = span_id

    def adopt_context(self, trace: Optional[str], parent: int) -> None:
        """Register a foreign parent span id for a trace (decoded from
        an inbound frame's TraceContext): every span this node records
        for that trace with no local parent links under it, joining the
        sender's tree. No-op when propagation is off."""
        if not (self.enabled and self.propagate) or not trace or not parent:
            return
        with self._lock:
            fg = self._foreign
            if trace not in fg and len(fg) >= _CTX_CAP:
                fg.pop(next(iter(fg)))
            fg[trace] = parent

    def wire_context(self, txid=None, seq=None):
        """Sender side of cross-node propagation: (trace_bytes, parent
        span id, sampled) for an outbound frame, or None when there is
        nothing to join (propagation off, tx unsampled, or no span
        recorded for the trace yet). trace_bytes is the raw 32-byte
        txid for tx traces, the UTF-8 trace id otherwise."""
        if not (self.enabled and self.propagate):
            return None
        if txid is not None and not self.sampled(txid):
            return None
        trace = _trace_id(txid, seq)
        if trace is None:
            return None
        with self._lock:
            parent = self._last.get(trace) or self._foreign.get(trace)
        if parent is None:
            return None
        if isinstance(txid, (bytes, bytearray)) and len(txid) == 32:
            trace_bytes = bytes(txid)
        else:
            trace_bytes = trace.encode()
        return trace_bytes, parent, True

    @staticmethod
    def trace_key(trace_bytes: bytes) -> Optional[str]:
        """Receiver-side inverse of wire_context's trace encoding."""
        if not trace_bytes:
            return None
        if len(trace_bytes) == 32:
            return trace_bytes.hex()
        try:
            return trace_bytes.decode()
        except UnicodeDecodeError:
            return None

    # -- export ------------------------------------------------------------

    def _snapshot_locked(self) -> list[tuple]:
        """Chronological ring contents; caller holds self._lock."""
        n = self._n
        if n <= self.capacity:
            return self._ring[:n]
        i = n % self.capacity
        return self._ring[i:] + self._ring[:i]

    def _snapshot(self) -> list[tuple]:
        with self._lock:
            return list(self._snapshot_locked())

    def chrome_trace(self, reset: bool = False) -> dict:
        """Chrome trace-event JSON (the `trace_dump` payload): complete
        ("X") and instant ("i") events over one pid, tid = recording
        thread, args carrying the causal ids (trace/span/parent) plus
        the span attrs. Loads directly in Perfetto / chrome://tracing.

        `reset=True` drains ATOMICALLY — snapshot and ring clear under
        one lock hold, so a span recorded concurrently lands in exactly
        one window, never between two (stage histograms survive a
        window reset; `reset()` clears those too)."""
        with self._lock:
            if self._parked:
                self._unpark_locked()
            recorded = self._n
            snap = list(self._snapshot_locked())
            if reset:
                self._ring = [None] * self.capacity
                self._n = 0
        events = []
        for rec in snap:
            (ph, name, cat, trace, span_id, parent, ts, dur, tid, attrs,
             cpu_us) = rec
            args = dict(attrs) if attrs else {}
            if cpu_us is not None:
                args["cpu_us"] = cpu_us
            if trace is not None:
                args["trace"] = trace
            args["span"] = span_id
            if parent is not None:
                args["parent"] = parent
            ev = {
                "name": name,
                "cat": cat,
                "ph": ph,
                "ts": ts,
                "pid": 1,
                "tid": tid,
                "args": args,
            }
            if ph == "X":
                ev["dur"] = dur
            else:
                ev["s"] = "t"  # instant scope: thread
            events.append(ev)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "recorded": recorded,
                "dropped": max(0, recorded - self.capacity),
                "sample": self.sample,
                # the clock: ts is perf_counter minus this epoch, and
                # anchors in a profiler trace carry this node tag
                "epoch_ns": int(self._epoch * 1e9),
                "node_tag": f"{self.node_tag:08x}",
                # the smallest non-zero `cpu_us` of the dump: an upper
                # bound on the step of the thread CPU clock, taken from
                # the data (Linux steps it in nanoseconds; a sandboxed
                # kernel that accounts CPU time by timer tick, as the
                # chip machines', 10 ms at a time: there one span reads
                # 0 or a whole tick and only sums mean anything); None
                # where no span ran
                "cpu_tick_us": min(
                    (rec[10] for rec in snap if rec[10]), default=None),
            },
        }

    def timeline(self, limit: int = 64) -> list[dict]:
        """Recent consensus/close/persist events, oldest first — the
        compact status timeline block (full detail lives in
        `trace_dump`). Scans the ring BACKWARDS with an early stop so
        a monitoring poll never copies the whole capacity-sized ring
        under the hot-path lock."""
        picked: list[tuple] = []
        with self._lock:
            n = self._n
            ring = self._ring
            start = n - 1
            stop = max(0, n - self.capacity)
            for j in range(start, stop - 1, -1):
                rec = ring[j % self.capacity]
                if rec[2] in _TIMELINE_CATS:
                    picked.append(rec)
                    if len(picked) >= limit:
                        break
        out = []
        for rec in reversed(picked):
            ph, name, cat, trace, _sid, _par, ts, dur, _tid, attrs, _cpu = rec
            ev = {"name": name, "cat": cat, "ts_ms": round(ts / 1000.0, 3)}
            if trace is not None:
                ev["trace"] = trace
            if ph == "X":
                ev["dur_ms"] = round(dur / 1000.0, 3)
            if attrs:
                ev.update(attrs)
            out.append(ev)
        return out

    # -- introspection / metrics -------------------------------------------

    def get_json(self) -> dict:
        """`trace_status` payload: knobs + ring occupancy + span-derived
        per-stage latency quantiles."""
        with self._lock:
            n = self._n
            stages = {name: h.get_json() for name, h in self.stage_hist.items()}
        return {
            "enabled": self.enabled,
            "capacity": self.capacity,
            "sample": self.sample,
            "propagate": self.propagate,
            "recorded": n,
            "buffered": min(n, self.capacity),
            "dropped": max(0, n - self.capacity),
            "stages": stages,
        }

    def stages(self, prefix: str, names) -> dict:
        """The stage histograms ``<prefix><name>`` for ``names``, keyed
        by the bare name: how the close pipeline and the ledger master
        fill their ``get_counts`` blocks, so that an interval is
        recorded into ONE histogram. A stage nothing has recorded yet
        (every one, with the tracer disabled) is left out."""
        with self._lock:
            return {n: self.stage_hist[prefix + n] for n in names
                    if prefix + n in self.stage_hist}

    def status_json(self, timeline: bool = True) -> dict:
        """One-call status block for the RPC surfaces: get_json plus —
        for ADMIN surfaces — the recent consensus/close timeline (it
        carries txids and peer key prefixes, so GUEST replies must pass
        timeline=False)."""
        out = self.get_json()
        if timeline:
            out["timeline"] = self.timeline()
        return out

    def statsd_hook(self) -> dict:
        """CollectorManager hook: span-derived p50/p90/p99 per stage as
        pull-gauges (`trace.<stage>.p50_ms: v|g` on the wire)."""
        out = {}
        with self._lock:
            hists = list(self.stage_hist.items())
        for name, h in hists:
            if not h.count:
                continue
            out[f"{name}.p50_ms"] = h.quantile(0.5)
            out[f"{name}.p90_ms"] = h.quantile(0.9)
            out[f"{name}.p99_ms"] = h.quantile(0.99)
        return out

    def reset(self) -> None:
        """Drop buffered events and stage histograms (admin
        `trace_dump` with reset=true; test isolation)."""
        with self._lock:
            self._ring = [None] * self.capacity
            self._n = 0
            self.stage_hist = {}
            self._foreign = {}
            self._last = {}
            self._parked.clear()


def parse_anchor(event_name: str) -> Optional[tuple[str, int]]:
    """``stellard.anchor tag=<8 hex> pc_ns=<n>`` -> (tag, pc_ns), or
    None for any other event of a profiler trace."""
    m = _ANCHOR.match(event_name)
    return (m.group(1), int(m.group(2))) if m else None


def place_on_trace_clock(anchors: list, epoch_ns: int):
    """-> f(ts_us) -> ns on the profiler's clock, for a ring whose
    ``otherData.epoch_ns`` is ``epoch_ns``. ``anchors`` are (pc_ns,
    trace_ns) pairs of ONE node tag: the ``perf_counter`` reading an
    anchor carries and where the profiler put the annotation. One anchor
    gives a constant offset; two or more also take out the drift
    between the two clocks (a line through the first and the last)."""
    if not anchors:
        raise ValueError("no clock anchor")
    anchors = sorted(anchors)
    (pc0, tr0), (pc1, tr1) = anchors[0], anchors[-1]
    rate = (tr1 - tr0) / (pc1 - pc0) if pc1 > pc0 else 1.0

    def place(ts_us: float) -> float:
        return tr0 + (epoch_ns + ts_us * 1000.0 - pc0) * rate

    return place


class _GcProbe:
    """The interpreter's collector, seen from inside: ONE
    ``gc.callbacks`` hook per process. ``install(tracer)`` is counted
    (a node's ``setup``, ``replay_range``/``replay_ledger`` on entry)
    and does nothing for a disabled tracer; ``remove(tracer)`` takes one
    installation back and the last one unhooks. Per generation:
    ``collections``, ``pause_s``, ``collected``. A full collection, and
    any collection of ``GC_SPAN_MIN_S`` or more, is also a
    ``gc.collect`` span in every installed tracer's ring (``len(gc.
    get_objects())`` is not taken: it is itself a scan).

    The hook runs for every young collection too (two calls, a clock
    read and three adds each; its measured share of the interpreter's
    time is in PERF.md)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # id(tracer) -> [weak reference, installations]: a tracer that
        # installed twice (a node and a replay_ledger run in its process)
        # still gets each collection once. Weak: a node dropped without
        # stop() must not pin its ring
        self._tracers: dict[int, list] = {}
        self.installs = 0
        self.collections = [0, 0, 0]
        self.pause_s = [0.0, 0.0, 0.0]
        self.collected = [0, 0, 0]
        self._t0 = 0.0

    def _prune_locked(self) -> None:
        for key in [k for k, (r, _n) in self._tracers.items()
                    if r() is None]:
            del self._tracers[key]

    def install(self, tracer: "Tracer") -> bool:
        if not tracer.enabled:
            return False
        with self._lock:
            self._prune_locked()
            slot = self._tracers.setdefault(
                id(tracer), [weakref.ref(tracer), 0])
            slot[1] += 1
            self.installs += 1
            if self._on_gc not in gc.callbacks:
                gc.callbacks.append(self._on_gc)
        return True

    def remove(self, tracer: "Tracer") -> None:
        with self._lock:
            slot = self._tracers.get(id(tracer))
            if slot is not None:
                slot[1] -= 1
                if slot[1] <= 0:
                    del self._tracers[id(tracer)]
            self._prune_locked()
            if not self._tracers and self._on_gc in gc.callbacks:
                gc.callbacks.remove(self._on_gc)

    @property
    def installed(self) -> int:
        return sum(n for _r, n in self._tracers.values())

    def _on_gc(self, phase: str, info: dict) -> None:
        # collections never nest and hold the interpreter lock, so one
        # slot for the start reading is enough
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        t1 = time.perf_counter()
        gen = info["generation"]
        dt = t1 - self._t0
        self.collections[gen] += 1
        self.pause_s[gen] += dt
        self.collected[gen] += info["collected"]
        if gen == 2 or dt >= GC_SPAN_MIN_S:
            for ref, _n in list(self._tracers.values()):
                tr = ref()
                if tr is not None:
                    # never complete(): this thread may hold tr's lock
                    tr.complete_unlocked(
                        "gc.collect", "runtime", self._t0, t1,
                        generation=gen, collected=info["collected"])

    def pause_total_s(self) -> float:
        return sum(self.pause_s)

    def get_json(self) -> dict:
        """Flat, for ``get_counts.runtime.gc`` and the ``gc`` collector
        hook (``gc.gen2_pause_s`` on ``/metrics``)."""
        out: dict = {"installed": self.installed,
                     "installs": self.installs}
        for g in range(3):
            out[f"gen{g}_collections"] = self.collections[g]
            out[f"gen{g}_pause_s"] = round(self.pause_s[g], 6)
            out[f"gen{g}_collected"] = self.collected[g]
        # who walks the old generation and how often (node/heapaging.py;
        # counted with `[trace] enabled=0` too)
        out.update(HEAP_AGING.get_json())
        return out


GC_PROBE = _GcProbe()


# the roles a thread of the node enters (doc/observability.md lists who
# enters which); `close` is the cpu_us of `close.total` and `other` is
# the process's clock less every role: neither is entered
ROLES = ("intake", "drain", "seal", "door", "fanout", "net", "upkeep")


class _ThreadRoles:
    """CPU seconds by role: ONE registry a process. A long-lived thread
    of the node enters under a role at the top of its target and leaves
    in a ``finally`` (``wrap()`` does both around a target). ``cpu_s()``
    reads ``clock_gettime`` of the threads that are entered, under the
    lock that leaving takes, so the clock of a thread that has ended is
    never read (``pthread_getcpuclockid`` of a dead thread is undefined
    behaviour): a leaving thread folds its own last reading into its
    role's total, and one that ended without leaving (a pool's worker,
    entered through the pool's ``initializer``) is folded in at its last
    reading by the next snapshot, which reads only threads that
    ``threading.enumerate()`` lists. A thread's seconds count from its
    entry. Entering and
    leaving are the only costs a thread pays; nothing is read between
    two snapshots (a close with the tracer enabled, ``get_counts``, a
    flush of ``/metrics``)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # ident -> [role, the thread's CPU clock id, its reading at
        # entry, its seconds since entry at the last snapshot]
        self._live: dict[int, list] = {}
        self._left = dict.fromkeys(ROLES, 0.0)
        if hasattr(os, "register_at_fork"):
            # a forked child (the spec workers) has none of these threads
            os.register_at_fork(after_in_child=self._reset)

    def _reset(self) -> None:
        self._lock = threading.Lock()
        self._live = {}
        self._left = dict.fromkeys(ROLES, 0.0)

    def enter(self, role: str) -> None:
        """The calling thread runs under ``role`` until ``leave()``."""
        if role not in self._left:
            raise ValueError(f"unknown thread role {role!r}")
        ident = threading.get_ident()
        # no per-thread clock id off Linux: such a thread's seconds
        # reach its role when it leaves
        clock_id = getattr(time, "pthread_getcpuclockid", None)
        clk = clock_id(ident) if clock_id is not None else None
        with self._lock:
            self._live[ident] = [role, clk, time.thread_time(), 0.0]

    def leave(self) -> None:
        """The calling thread is done: its seconds since ``enter()``
        stay in its role's total. A thread that never entered is fine."""
        with self._lock:
            was = self._live.pop(threading.get_ident(), None)
            if was is not None:
                self._left[was[0]] += time.thread_time() - was[2]

    def wrap(self, role: str, target):
        """``threading.Thread(target=THREAD_ROLES.wrap(role, loop))``:
        enter at the top of the target, leave in a ``finally``."""
        def run(*args, **kwargs):
            self.enter(role)
            try:
                return target(*args, **kwargs)
            finally:
                self.leave()
        return run

    def role_of(self, ident: int) -> Optional[str]:
        was = self._live.get(ident)
        return was[0] if was is not None else None

    def credit(self, seconds: Optional[float]) -> None:
        """CPU seconds a short-lived helper ran on the calling thread's
        behalf (the device call's deadline thread) count under the
        caller's role, if it has one."""
        if seconds:
            with self._lock:
                was = self._live.get(threading.get_ident())
                if was is not None:
                    self._left[was[0]] += seconds

    def _read(self) -> tuple[dict[str, float], dict[str, int]]:
        """(role -> CPU seconds so far, role -> threads entered now):
        the threads that left, plus one clock read of each entered."""
        gettime = time.clock_gettime
        threads = dict.fromkeys(ROLES, 0)
        with self._lock:
            # listed under the lock: a thread that enters now waits for it
            alive = {t.ident for t in threading.enumerate()}
            for ident in [i for i in self._live if i not in alive]:
                role, _clk, _c0, seen = self._live.pop(ident)
                self._left[role] += seen
            cpu = dict(self._left)
            for was in self._live.values():
                role, clk, c0, _seen = was
                if clk is not None:
                    was[3] = gettime(clk) - c0
                    cpu[role] += was[3]
                threads[role] += 1
        return cpu, threads

    def cpu_s(self) -> dict[str, float]:
        return self._read()[0]

    def marks(self) -> tuple:
        """(perf_counter, the roles' seconds in ``ROLES`` order, process
        CPU seconds): what a close cycle's differences are taken of. The
        process's clock is read LAST, so that over a cycle the roles
        cannot sum to more than the process ran."""
        now = time.perf_counter()
        cpu = self.cpu_s()
        return now, tuple(cpu[r] for r in ROLES), time.process_time()

    def get_json(self) -> dict:
        """``get_counts.runtime.threads``: ``{role: {threads, cpu_s}}``
        and ``process_cpu_s``."""
        cpu, threads = self._read()
        out: dict = {r: {"threads": threads[r],
                         "cpu_s": round(cpu[r], 6)} for r in ROLES}
        out["process_cpu_s"] = round(time.process_time(), 6)
        return out

    def flat_json(self) -> dict:
        """The ``threads`` collector hook: ``/metrics``
        ``threads.<role>_cpu_s``, ``threads.<role>_threads``,
        ``threads.process_cpu_s``."""
        out: dict = {}
        for role, val in self.get_json().items():
            if isinstance(val, dict):
                out[f"{role}_cpu_s"] = val["cpu_s"]
                out[f"{role}_threads"] = val["threads"]
            else:
                out[role] = val
        return out


THREAD_ROLES = _ThreadRoles()


# module-level default: subsystems constructed outside a Node (unit
# tests, embedders) still trace into a shared, bounded recorder; a Node
# builds its own Tracer from [trace] and installs it on the subsystems
# it owns, so two nodes in one process don't interleave rings
_DEFAULT = Tracer()


def get_tracer() -> Tracer:
    return _DEFAULT
