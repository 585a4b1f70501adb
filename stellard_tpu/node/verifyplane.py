"""VerifyPlane: the coalescing device-batched signature pipeline.

This is the north-star seam (SURVEY §2.9 mapping #1): the reference
verifies each signature synchronously inside its own job
(PeerImp::checkTransaction → STTx::checkSign → libsodium); here,
verification requests from concurrent jobs are coalesced across an
adaptive window and dispatched as ONE device program over the whole
batch (crypto.backend.BatchVerifier).

Dispatch is LATENCY-AWARE (VERDICT r2 #1b): the plane continuously
measures both backends on the batches it actually runs — a per-signature
EWMA for the threaded CPU path, a per-pad-bucket EWMA for the device
kernel (whose cost is dominated by a fixed per-invocation latency) — and
routes each batch to whichever model predicts faster. Small/trickled
batches therefore stay on the CPU even when a device is configured; the
device wins exactly where it is faster. Per-batch latencies are kept as
histograms per backend (the SURVEY §5 tracing ask) and exported through
get_json.

Callers either:
- `submit(req) -> Future[bool]` — async, coalesced (the JobQueue path),
- `verify_many(reqs) -> ndarray` — blocking whole-batch (consensus close
  verifying a round's validations at once).
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import Future
from typing import Optional, Sequence

import numpy as np

from ..crypto.backend import BatchVerifier, VerifyRequest, make_verifier
from ..utils.devicewatch import (
    DeviceWedged,
    call_with_deadline,
    resolve_timeouts,
)
from .heapaging import HEAP_AGING
from .metrics import LatencyHist
from .tracer import THREAD_ROLES

log = logging.getLogger("stellard.device")

__all__ = ["VerifyPlane"]

# per-batch latency bucket upper bounds (ms); the +inf overflow bucket
# is implicit in LatencyHist
_HIST_BOUNDS = (1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0, 500.0,
                1000.0)


class _LatencyModel:
    """Measured-cost models for the routing decision, generalized from
    cpu-vs-device to cpu + N device ARMS: with a multi-chip mesh
    configured the plane carries a 1-chip arm ("dev1") and an N-chip
    arm ("devN") of the same program, so small batches stay on the CPU,
    medium batches on one chip, and only batches that amortize the
    collective go wide (ISSUE 15 three-way routing)."""

    # after this many CPU-routed eligible batches, retry a device arm
    # once (load characteristics drift; a one-shot loss must not be
    # forever)
    REEXPLORE_EVERY = 512
    # the same the other way round: after this many batches in a row
    # priced to a device arm that is NOT far ahead, one goes to the host,
    # so that the model hears from both arms. A closed loop whose every
    # batch rides the chip forms no host batch that could teach the
    # host's price, and a wrong one would otherwise stand until the loop
    # breaks (PERF.md section 7, PR 34: 24 batches of 96 in a row)
    PROBE_HOST_EVERY = 4
    # a host sample counts as this many times the running price at most:
    # a batch that sat behind a close or a dump is a stall, not a price.
    # One such sample then moves the price by a quarter; a host that
    # really slowed down is believed a quarter more with every batch
    STALL_CAP = 2.0

    def __init__(self, min_device_batch: int,
                 device_arms: Sequence[str] = ("device",)):
        self.min_device_batch = min_device_batch
        self.device_arms = tuple(device_arms)
        # CPU: cost ~ linear in batch size
        self.cpu_persig_ms: Optional[float] = None
        # device: cost ~ flat per pad-bucket (kernel latency dominates),
        # one bucket map per arm
        self._bucket_ms: dict[str, dict[int, float]] = {
            a: {} for a in self.device_arms
        }
        # (arm, bucket)s that have absorbed their first (compile-laden)
        # sample
        self._device_warm: set[tuple[str, int]] = set()
        self._since: dict[str, int] = {a: 0 for a in self.device_arms}
        # batches priced to a device arm since the host arm last reported
        self._device_run = 0
        self.lock = threading.Lock()

    @property
    def device_bucket_ms(self) -> dict[int, float]:
        """Legacy single-arm view: the primary device arm's buckets."""
        return self._bucket_ms[self.device_arms[-1]]

    @property
    def _since_device(self) -> int:
        return self._since[self.device_arms[-1]]

    @staticmethod
    def _bucket(n: int, lo: int) -> int:
        size = lo
        while size < n:
            size *= 2
        return size

    def observe_cpu(self, n: int, ms: float) -> None:
        if n <= 0:
            return
        with self.lock:
            per = ms / n
            self._device_run = 0
            if self.cpu_persig_ms is None:
                self.cpu_persig_ms = per
            else:
                per = min(per, self.STALL_CAP * self.cpu_persig_ms)
                self.cpu_persig_ms += 0.25 * (per - self.cpu_persig_ms)

    def observe_device(self, n: int, ms: float,
                       arm: Optional[str] = None) -> None:
        arm = arm if arm is not None else self.device_arms[-1]
        b = self._bucket(max(n, 1), self.min_device_batch)
        with self.lock:
            self._since[arm] = 0
            if (arm, b) not in self._device_warm:
                # first sample per bucket includes XLA compilation —
                # recording it would poison the model and route every
                # later batch to the CPU; discard it and measure the
                # steady state from the second sample on
                self._device_warm.add((arm, b))
                return
            buckets = self._bucket_ms[arm]
            cur = buckets.get(b)
            buckets[b] = ms if cur is None else cur + 0.25 * (ms - cur)

    def expected_cpu_ms(self, n: int) -> Optional[float]:
        with self.lock:
            if self.cpu_persig_ms is None:
                return None
            return self.cpu_persig_ms * n

    def expected_device_ms(self, n: int,
                           arm: Optional[str] = None) -> Optional[float]:
        arm = arm if arm is not None else self.device_arms[-1]
        b = self._bucket(max(n, 1), self.min_device_batch)
        with self.lock:
            buckets = self._bucket_ms[arm]
            if b in buckets:
                return buckets[b]
            # nearest measured bucket as an estimate; device cost is
            # near-flat, so any measurement beats none
            if buckets:
                near = min(buckets, key=lambda k: abs(k - b))
                return buckets[near]
            return None

    def route(self, n: int, count: bool = True,
              arms: Optional[Sequence[str]] = None) -> str:
        """Pick the side for this batch: ``"cpu"`` or a device arm
        name (``decide`` without its evidence)."""
        return self.decide(n, count=count, arms=arms)[0]

    def decide(self, n: int, count: bool = True,
               arms: Optional[Sequence[str]] = None) -> tuple:
        """-> (side, why, expected device ms, expected cpu ms): the side
        for this batch (``"cpu"`` or a device arm name) and the evidence
        behind it, which rides the ``verify.batch`` span. ``why`` is one
        word: ``small`` (under min_device_batch, or no device arm),
        ``explore`` (an unmeasured arm, or the periodic retry of a
        losing one, or of the host behind a run of device batches),
        ``priced`` (both sides measured, the cheaper one taken).
        Unmeasured arms are explored optimistically (in declared order)
        once a batch reaches min_device_batch, after which real
        measurements drive every later decision. `count=False` asks the
        same question without advancing the re-exploration counters
        (the coalescing-window decision polls this every wake-up and
        must not inflate the re-explore cadence)."""
        avail = [a for a in (arms if arms is not None else self.device_arms)
                 if a in self._bucket_ms]
        if n < self.min_device_batch or not avail:
            return "cpu", "small", None, None
        costs: dict[str, float] = {}
        for a in avail:
            d = self.expected_device_ms(n, a)
            if d is None:
                # explore: one measurement teaches the model
                return a, "explore", None, None
            costs[a] = d
        best_arm = min(costs, key=lambda a: costs[a])
        best = costs[best_arm]
        cpu = self.expected_cpu_ms(n)
        if cpu is None:
            return "cpu", "explore", best, None  # CPU unmeasured: measure it too
        if best < cpu:
            if count and cpu < best * 4.0:
                with self.lock:
                    self._device_run += 1
                    if self._device_run > self.PROBE_HOST_EVERY:
                        self._device_run = 0
                        return "cpu", "explore", best, cpu
            return best_arm, "priced", best, cpu
        if not count:
            return "cpu", "priced", best, cpu
        # periodic re-exploration so a stale loss can be unlearned — but
        # only within striking distance: a ~300 ms kernel invocation must
        # never be retried on a 64-sig batch it cannot possibly win
        for a in avail:
            if cpu * 4.0 < costs[a]:
                continue
            with self.lock:
                self._since[a] += 1
                if self._since[a] >= self.REEXPLORE_EVERY:
                    self._since[a] = 0
                    return a, "explore", costs[a], cpu
        return "cpu", "priced", best, cpu

    def use_device(self, n: int, count: bool = True) -> bool:
        return self.route(n, count=count) != "cpu"

    def get_json(self) -> dict:
        with self.lock:
            return {
                "cpu_persig_ms": self.cpu_persig_ms,
                "device_bucket_ms": dict(
                    self._bucket_ms[self.device_arms[-1]]
                ),
                "arms": {a: dict(b) for a, b in self._bucket_ms.items()},
            }


# why a batch ran on the host arm (the `why` of its `verify.batch` span)
_HOST_REASONS = ("small", "priced", "explore", "cold", "wedged", "nodevice")


class VerifyPlane:
    def __init__(
        self,
        backend: str = "cpu",
        window_ms: float = 2.0,
        max_batch: int = 16384,
        min_device_batch: int = 64,
        cpu_fallback: Optional[BatchVerifier] = None,
        device_first_timeout: Optional[float] = None,
        device_warm_timeout: Optional[float] = None,
        tracer=None,
        backend_opts: Optional[dict] = None,
        routing: str = "cost",
    ):
        from ..crypto.backend import mesh_wants_width
        from .tracer import get_tracer

        self.tracer = tracer if tracer is not None else get_tracer()
        self.backend_name = backend
        # backend_opts flow to the factory VERBATIM (and unknown keys
        # fail loudly there): this is the config->plane plumbing that
        # makes [signature_backend] options like mesh= reachable —
        # before it, make_verifier(backend) dropped every kwarg and
        # TpuVerifier's knobs were dead config (ISSUE 15)
        self.backend_opts = dict(backend_opts or {})
        self.verifier: BatchVerifier = make_verifier(
            backend, **self.backend_opts
        )
        # the 1-chip arm of the three-way cpu/1-chip/N-chip routing:
        # when the opts request a multi-chip mesh, the same program is
        # also built at width 1, and the latency model measures both
        # arms — medium batches take one chip, only batches that
        # amortize the collective go wide
        self._one_chip: Optional[BatchVerifier] = None
        if "mesh" in self.backend_opts and mesh_wants_width(
            self.backend_opts["mesh"]
        ):
            one_opts = dict(self.backend_opts)
            one_opts["mesh"] = "0"
            self._one_chip = make_verifier(backend, **one_opts)
        self.cpu: BatchVerifier = cpu_fallback or (
            self.verifier if backend == "cpu" else make_verifier("cpu")
        )
        self.window = window_ms / 1000.0
        self.max_batch = max_batch
        self.min_device_batch = min_device_batch
        arms = ("dev1", "devN") if self._one_chip is not None else ("device",)
        self.model = _LatencyModel(min_device_batch, device_arms=arms)
        self._device_capable = backend != "cpu"
        # routing=device forces every eligible (>= min_device_batch)
        # batch onto the widest device arm — the anti-vacuity mode the
        # meshsmoke gate uses; cost (default) is the measured-latency
        # routing.
        if routing not in ("cost", "device"):
            raise ValueError(
                f"verify routing must be cost|device, got {routing!r}"
            )
        self.routing = routing
        self._route_by_cost = routing != "device"
        # device-wedge watchdog deadlines (utils.devicewatch): the first
        # call to a pad-bucket shape legitimately compiles (~1-3 min on
        # chip), so unseen shapes get the generous deadline and warmed
        # shapes the tight one. On overrun the device is dead for the
        # process and every batch (including the stalled one, re-run on
        # the CPU side) still gets verified.
        self._t_first, self._t_warm = resolve_timeouts(
            device_first_timeout, device_warm_timeout
        )
        # warm pad-bucket shapes per device arm (each arm compiles its
        # own programs: a warm wide shape says nothing about the 1-chip
        # program of the same size)
        self._warm_buckets: dict[str, set[int]] = {
            a: set() for a in self.model.device_arms
        }
        self.device_wedged = False
        # a device arm that RAISED (compiler refusal, runtime error):
        # not a hang, but just as fatal to the plane — sticky like the
        # wedge, and the text of the first failure rides get_json
        self.device_failed = False
        self.device_error: Optional[str] = None
        # a prewarm that raised leaves a node that merely looks cold;
        # its error text rides get_json so operators and gates see it
        self.prewarm_error: Optional[str] = None
        # while a prewarm runs, traffic routes to the CPU side — the
        # device must never pay its first (compile-laden) invocation on
        # live batches
        self._prewarm_pending = False

        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._pending: list[tuple[VerifyRequest, Future]] = []
        self._stopping = False
        self.batches = 0
        self.verified = 0
        self.device_batches = 0
        self.cpu_batches = 0
        # per-SIGNATURE routing counters: a bench leg's "device share"
        # (device_sigs / verified) proves the device actually did work —
        # latency-aware routing can otherwise zero the device out while
        # the leg still reports a healthy ~1.0 ratio (VERDICT r3 weak #6)
        self.device_sigs = 0
        self.cpu_sigs = 0
        # batches at or above min_device_batch that nonetheless ran on
        # the CPU arm (prewarm pending, cost routing, wedge, failure):
        # under routing=device a healthy warm plane keeps this at zero
        self.cpu_eligible_batches = 0
        # signatures the host arm verified, by the reason the batch
        # stayed there (the `why` of its `verify.batch` span): which of
        # batch size and price keeps the chip idle
        self.host_sigs_by_why: dict[str, int] = {}
        # per-arm routing counters (provenance: which kernel width the
        # device traffic actually ran on)
        self._arm_batches: dict[str, int] = {
            a: 0 for a in self.model.device_arms
        }
        self._arm_sigs: dict[str, int] = {
            a: 0 for a in self.model.device_arms
        }
        self._hist: dict[str, LatencyHist] = {
            "cpu": LatencyHist(bounds=_HIST_BOUNDS),
            "device": LatencyHist(bounds=_HIST_BOUNDS),
        }
        self._flusher = threading.Thread(
            target=THREAD_ROLES.wrap("intake", self._flush_loop),
            name="verify-plane", daemon=True
        )
        self._flusher.start()

    # -- async coalesced path --------------------------------------------

    def submit(self, req: VerifyRequest) -> "Future[bool]":
        fut: Future = Future()
        with self._lock:
            self._pending.append((req, fut))
            if len(self._pending) >= self.max_batch:
                self._cv.notify()
        return fut

    def _flush_loop(self) -> None:
        while True:
            with self._lock:
                while not self._pending and not self._stopping:
                    self._cv.wait(timeout=0.05)
                if self._stopping and not self._pending:
                    return
                # coalescing window: wait for more arrivals while the
                # backlog is still below a device-worthwhile batch AND the
                # device would win at the larger size (holding a batch the
                # CPU can clear immediately only adds latency)
                if len(self._pending) < self.max_batch and (
                    self._device_capable
                    and not self._prewarm_pending
                    and (
                        not self._route_by_cost
                        or self.model.route(
                            max(len(self._pending), self.min_device_batch),
                            count=False,
                            arms=self._device_arms(),
                        ) != "cpu"
                    )
                ):
                    self._cv.wait(timeout=self.window)
                batch = self._pending[: self.max_batch]
                self._pending = self._pending[self.max_batch :]
            reqs = [r for r, _ in batch]
            try:
                results = self.verify_many(reqs, source="intake")
            except Exception as exc:  # noqa: BLE001 — fail the futures, not the plane
                for _, fut in batch:
                    if not fut.done():
                        fut.set_exception(exc)
                continue
            for (_, fut), ok in zip(batch, results):
                fut.set_result(bool(ok))

    # -- blocking whole-batch path ---------------------------------------

    def _record(self, kind: str, ms: float) -> None:
        self._hist[kind].record(ms)

    def _device_arms(self) -> tuple:
        """The device arms currently worth routing between. Once the
        wide verifier RESOLVES to a single device (mesh= wider than the
        box), the 1-chip arm is the identical program — collapse it."""
        if (self._one_chip is not None
                and getattr(self.verifier, "n_devices", 0) == 1):
            self._one_chip = None
        if self._one_chip is None and len(self.model.device_arms) > 1:
            return self.model.device_arms[-1:]
        return self.model.device_arms

    def _verifier_of(self, arm: str) -> BatchVerifier:
        if arm == "dev1" and self._one_chip is not None:
            return self._one_chip
        return self.verifier

    def _pad_buckets(self, n: int, arm: Optional[str] = None) -> set[int]:
        """Pad-bucket shapes the arm's verifier will compile for a batch
        of n (one chunk per max_batch, each padded per its own policy)."""
        ver = self._verifier_of(arm) if arm is not None else self.verifier
        pad = getattr(ver, "_pad_size", None)
        lo = getattr(ver, "min_batch", self.min_device_batch)
        hi = getattr(ver, "max_batch", self.max_batch)
        buckets = set()
        for start in range(0, n, hi):
            chunk = min(hi, n - start)
            buckets.add(pad(chunk, lo, hi) if pad else chunk)
        return buckets

    def _device_deadline(self, n: int, arm: Optional[str] = None) -> float:
        """Generous while any chunk's pad-bucket shape is uncompiled,
        tight (per chunk) once every shape is warm."""
        arm = arm if arm is not None else self.model.device_arms[-1]
        if self._pad_buckets(n, arm) - self._warm_buckets[arm]:
            return self._t_first
        ver = self._verifier_of(arm)
        hi = getattr(ver, "max_batch", self.max_batch)
        nchunks = max(1, -(-n // max(1, hi)))
        return self._t_warm * nchunks

    def _mark_warm(self, n: int, arm: Optional[str] = None) -> None:
        arm = arm if arm is not None else self.model.device_arms[-1]
        self._warm_buckets[arm] |= self._pad_buckets(n, arm)

    def start_prewarm(
        self, sizes: Optional[Sequence[int]] = None, rounds: int = 2
    ) -> threading.Thread:
        """Compile and measure the device's pad-bucket shapes OFF the
        traffic path. Until the thread finishes, every live batch routes
        to the CPU side; afterwards the routing model holds real
        steady-state device measurements (the first sample per bucket is
        compile-laden and discarded by observe_device). The reference
        needs no analog — libsodium is ready at link time; XLA
        compilation is the TPU build's equivalent and belongs in node
        startup, never inside live traffic. Join the returned thread for
        a deterministic warm start (bench legs do)."""
        if sizes is None:
            # derive from this plane's own routing range: every pad
            # bucket between the smallest batch the model can route to
            # the device and the largest it can coalesce — live traffic
            # must find EVERY shape warm (under the TPU "max" pad
            # policy the whole ladder collapses to one canonical shape)
            lo = max(
                self.min_device_batch,
                getattr(self.verifier, "min_batch", self.min_device_batch),
            )
            ladder = []
            size = lo
            while size < self.max_batch:
                ladder.append(size)
                size *= 2
            ladder.append(self.max_batch)
            sizes = sorted(set(ladder))
        if self._device_capable:
            self._prewarm_pending = True
        # set-up on the timeline: `node.prewarm` is this thread's life,
        # with one `prewarm.program` child for every program the
        # process built or loaded from the compile cache meanwhile (name,
        # cache_hit; what utils.xlacache.COMPILES times)
        from ..utils.xlacache import COMPILES

        tr = self.tracer
        span = tr.begin("node.prewarm", "setup", sizes=list(sizes),
                        backend=self.backend_name)

        def on_program(name: str, hit: bool, secs: float) -> None:
            t1 = time.perf_counter()
            tr.complete("prewarm.program", "setup", t1 - secs, t1,
                        parent=span, program=name, cache_hit=bool(hit))

        if span is not None and self._device_capable:
            COMPILES.observers.append(on_program)

        def run() -> None:
            try:
                if not self._device_capable:
                    return
                req = VerifyRequest(b"\x66" * 32, b"\x77" * 32, b"\x88" * 64)
                # warm EVERY device arm the router can pick: the 1-chip
                # and N-chip programs compile separately. Forced-device
                # mode only ever routes the widest arm, so only that
                # one needs warming. WIDEST FIRST, re-reading the live
                # arm set between arms: resolving the wide program may
                # collapse the 1-chip arm (mesh wider than the box), and
                # a stale snapshot would warm a duplicate width-1
                # program nothing will ever route to.
                for size in sizes:
                    reqs = [req] * size
                    warmed: set = set()
                    while True:
                        arms = self._device_arms()
                        if not self._route_by_cost:
                            arms = arms[-1:]
                        todo = [a for a in reversed(arms)
                                if a not in warmed]
                        if not todo:
                            break
                        arm = todo[0]
                        warmed.add(arm)
                        ver = self._verifier_of(arm)
                        for _ in range(max(2, rounds)):
                            t0 = time.perf_counter()
                            call_with_deadline(
                                lambda v=ver: v.verify_batch(reqs),
                                self._device_deadline(size, arm),
                                label="verify-prewarm",
                            )
                            ms = (time.perf_counter() - t0) * 1000.0
                            self._mark_warm(size, arm)
                            self.model.observe_device(size, ms, arm=arm)
            except DeviceWedged as exc:
                self._device_capable = False
                self.device_wedged = True
                log.error("verify prewarm: %s — device plane disabled", exc)
            except Exception as exc:  # noqa: BLE001 — a prewarm failure must not kill startup
                self.prewarm_error = f"{type(exc).__name__}: {exc}"[:2000]
                log.exception("verify prewarm FAILED; device unwarmed")
            finally:
                self._prewarm_pending = False
                if on_program in COMPILES.observers:
                    COMPILES.observers.remove(on_program)
                tr.end(span, error=self.prewarm_error,
                       wedged=self.device_wedged)
                # the traced programs' objects live as long as the plane
                HEAP_AGING.age()

        t = threading.Thread(target=THREAD_ROLES.wrap("upkeep", run),
                             name="verify-prewarm", daemon=True)
        t.start()
        return t

    def verify_many(self, reqs: Sequence[VerifyRequest],
                    source: Optional[str] = None) -> np.ndarray:
        """``source`` names the caller on the batch's span, so a reader
        can tell whose batch the router priced: ``intake`` (the
        coalescing flusher and the synchronous door), ``relay`` (a
        network read's burst of relayed transactions), ``proposal``,
        ``validation`` (``ValidatorNode._verify``); a caller that does
        not say (a replay, a check) leaves the attribute off."""
        if not reqs:
            return np.zeros(0, bool)
        n = len(reqs)
        # the router's evidence, on the batch's span: `why` is one word
        # (small | priced | explore from the cost model; forced under
        # routing=device; cold while the prewarm runs; wedged once the
        # device plane is retired; nodevice on a cpu-only backend)
        arm, exp_dev, exp_cpu = "cpu", None, None
        if self._device_capable and not self._prewarm_pending:
            if self._route_by_cost:
                arm, why, exp_dev, exp_cpu = self.model.decide(
                    n, arms=self._device_arms())
            elif n >= self.min_device_batch:
                # forced-device mode: the widest available arm
                arm, why = self._device_arms()[-1], "forced"
            else:
                why = "small"
        elif self._prewarm_pending:
            why = "cold"
        elif self.device_wedged or self.device_failed:
            why = "wedged"
        else:
            why = "nodevice"
        evidence = {"why": why}
        if source is not None:
            evidence["source"] = source
        if exp_dev is not None:
            evidence["exp_device_ms"] = round(exp_dev, 3)
        if exp_cpu is not None:
            evidence["exp_cpu_ms"] = round(exp_cpu, 3)
        wedged_now = False
        if arm != "cpu":
            ver = self._verifier_of(arm)
            cpu = self.tracer.thread_cpu
            ran: list = []

            def device_call():
                # on the deadline's helper thread: what it ran (packing,
                # dispatch, unpacking) is the batch's host CPU; the rest
                # of the batch's wall is the wait for the chip
                c = cpu()
                res = ver.verify_batch(reqs)
                ran.append(self.tracer.cpu_since(c))
                return res

            # the thread clock is read OUTSIDE [t0, t1]: that interval is
            # the router's evidence (observe_device / observe_cpu) and a
            # read is a system call of microseconds on some hosts
            c0 = cpu()
            t0 = time.perf_counter()
            try:
                out = call_with_deadline(
                    device_call,
                    self._device_deadline(n, arm),
                    label="verify-device",
                )
                t1 = time.perf_counter()
                cpu_s = self.tracer.cpu_since(c0)
                if cpu_s is not None and ran:
                    cpu_s += ran[0]
                    # the helper ran on this thread's behalf: its seconds
                    # belong to this thread's role (the flusher's intake)
                    THREAD_ROLES.credit(ran[0])
                ms = (t1 - t0) * 1000.0
                self._mark_warm(n, arm)
                self.model.observe_device(n, ms, arm=arm)
                self.device_batches += 1
                self.device_sigs += n
                self._arm_batches[arm] = self._arm_batches.get(arm, 0) + 1
                self._arm_sigs[arm] = self._arm_sigs.get(arm, 0) + n
                self._record("device", ms)
                self.batches += 1
                self.verified += n
                # batch formation + routing decision evidence: size and
                # the arm the latency model picked (device width rides
                # the name), kernel wall time as the span duration
                self.tracer.complete(
                    "verify.batch", "verify", t0, t1, cpu_s=cpu_s,
                    n=n, routed=arm, **evidence,
                )
                return out
            except DeviceWedged as exc:
                # wedged device: the device plane is dead for the
                # process (BOTH arms — they share the runtime); this
                # batch (and all future ones) verifies on the CPU
                self._device_capable = False
                self.device_wedged = True
                wedged_now = True
                evidence["why"] = "wedged"
                log.error("verify plane: %s — falling back to CPU", exc)
            except Exception as exc:  # noqa: BLE001 — a device failure is not a verdict
                # the arm raised instead of answering: no signature in
                # this batch has been judged, so it is verified on the
                # CPU arm, and the device plane is retired as loudly
                # and as stickily as on a wedge
                self._device_capable = False
                self.device_failed = True
                self.device_error = f"{type(exc).__name__}: {exc}"[:2000]
                wedged_now = True
                evidence["why"] = "wedged"
                log.exception(
                    "verify plane: device arm %s RAISED on a %d-signature "
                    "batch — device plane disabled, falling back to CPU",
                    arm, n,
                )
        if n >= self.min_device_batch:
            self.cpu_eligible_batches += 1
        c0 = self.tracer.thread_cpu()  # outside [t0, t1], as above
        t0 = time.perf_counter()
        out = self.cpu.verify_batch(reqs)
        t1 = time.perf_counter()
        cpu_s = self.tracer.cpu_since(c0)
        ms = (t1 - t0) * 1000.0
        # tiny batches (the synchronous RPC-submit path is n=1) carry
        # un-amortized fixed overhead; folding them into the per-sig
        # EWMA would inflate expected_cpu_ms for LARGE batches and bias
        # routing toward the device on evidence that doesn't transfer
        if n >= 8:
            self.model.observe_cpu(n, ms)
        self.cpu_batches += 1
        self.cpu_sigs += n
        why = evidence["why"]
        self.host_sigs_by_why[why] = self.host_sigs_by_why.get(why, 0) + n
        self._record("cpu", ms)
        self.batches += 1
        self.verified += n
        self.tracer.complete(
            "verify.batch", "verify", t0, t1, cpu_s=cpu_s, n=n,
            routed="cpu", **evidence,
            **({"wedged_fallback": True} if wedged_now else {}),
        )
        return out

    def stop(self) -> None:
        with self._lock:
            self._stopping = True
            self._cv.notify_all()
        self._flusher.join(timeout=10)

    def _transfer_json(self):
        """Aggregate the device arms' TransferMeters (N-chip inner plus
        the 1-chip arm when built). None for host-only backends."""
        agg = None
        for v in (self.verifier, self._one_chip):
            meter = getattr(v, "transfers", None) if v is not None else None
            if meter is None:
                continue
            j = meter.get_json()
            if agg is None:
                agg = dict(j)
            else:
                for k, val in j.items():
                    agg[k] = agg.get(k, 0) + val
        return agg

    def get_json(self) -> dict:
        model = self.model.get_json()
        describe = getattr(self.verifier, "describe", None)
        return {
            "backend": self.backend_name,
            "routing": self.routing,
            # mesh provenance: requested width, effective width,
            # devices visible and the kernel actually selected — a
            # BENCH/ops reader must see what ran (ISSUE 15)
            "mesh": describe() if describe is not None else None,
            # transfer honesty: host<->device traffic across both device
            # arms — per-close deltas of this block pin residency
            "transfers": self._transfer_json(),
            "arms": {
                a: {
                    "batches": self._arm_batches.get(a, 0),
                    "sigs": self._arm_sigs.get(a, 0),
                }
                for a in self.model.device_arms
            },
            # which host implementation fills the cpu side (native C++
            # batch kernel vs per-signature host library) — a silent
            # toolchain degrade must be visible to operators (this dict
            # is embedded in the get_counts / print RPC replies)
            "host_impl": getattr(self.cpu, "impl", "?"),
            "batches": self.batches,
            "verified": self.verified,
            "device_batches": self.device_batches,
            "cpu_batches": self.cpu_batches,
            "device_sigs": self.device_sigs,
            "cpu_sigs": self.cpu_sigs,
            "cpu_eligible_batches": self.cpu_eligible_batches,
            # why the host arm's signatures stayed there: too small a
            # batch for the chip, priced out by the cost model, ...
            **{f"host_{why}_sigs": self.host_sigs_by_why.get(why, 0)
               for why in _HOST_REASONS},
            "device_wedged": self.device_wedged,
            "device_failed": self.device_failed,
            "device_error": self.device_error,
            "prewarm_error": self.prewarm_error,
            "device_share": (
                round(self.device_sigs / self.verified, 4)
                if self.verified
                else 0.0
            ),
            "pending": len(self._pending),
            "model": model,
            "latency_histogram_ms": {
                "edges": list(_HIST_BOUNDS),
                "cpu": list(self._hist["cpu"].counts),
                "device": list(self._hist["device"].counts),
                "cpu_quantiles": self._hist["cpu"].get_json(),
                "device_quantiles": self._hist["device"].get_json(),
            },
        }
