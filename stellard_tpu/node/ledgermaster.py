"""LedgerMaster: the ledger-chain state machine.

Reference: src/ripple_app/ledger/LedgerMaster.cpp (1469 LoC) — tracks the
current open ledger, last closed ledger and last validated ledger
(LedgerHolder triples), holds transactions that can't apply yet
(terPRE_SEQ et al.) for retry on the next ledger, and accepts a ledger as
validated once a quorum of trusted validations arrives (checkAccept,
:705-750). Also CanonicalTXSet (misc/CanonicalTXSet.cpp): the salted
canonical application order used when a closed ledger's tx set is
applied.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional

from ..engine.deltareplay import FALLBACK_REASONS, SpecPolicy
from ..engine.engine import TransactionEngine, TxParams, merge_tally
from ..node.hashrouter import SF_SIGGOOD
from ..protocol.sttx import SerializedTransaction
from ..protocol.ter import TER
from ..state.ledger import Ledger
from ..state.shamap import inner_node_cache
from .metrics import AtomicCounters
from .tracer import ROLES, THREAD_ROLES, get_tracer

__all__ = ["LedgerMaster", "CanonicalTXSet", "LEDGER_TOTAL_PASSES"]

# reference: applyTransactions retry sizing (LedgerConsensus.cpp:1935-2070)
LEDGER_TOTAL_PASSES = 4

# held-pile bounds (reference: mHeldTransactions is unbounded — a
# single-account sequence-gap flood pinned memory forever): entries
# expire after this many closes, and the pile itself is capped with
# FIFO eviction. With the TxQ enabled the pile is absorbed into the
# fee-ordered queue instead and these bounds are the fallback path.
HELD_EXPIRE_LEDGERS = 16
HELD_CAP = 1024


class CanonicalTXSet:
    """Salted canonical ordering (reference: misc/CanonicalTXSet.{h,cpp}):
    sort key = (account XOR salt, sequence, txid); the salt is the parent
    ledger hash so the order is unpredictable to submitters but identical
    on every node."""

    def __init__(self, salt: bytes):
        self.salt = salt
        self._map: dict[tuple, SerializedTransaction] = {}

    def insert(self, tx: SerializedTransaction) -> None:
        acct = int.from_bytes(tx.account, "big")
        salt = int.from_bytes(self.salt[:20], "big")
        self._map[(acct ^ salt, tx.sequence, tx.txid())] = tx

    def erase(self, key: tuple) -> None:
        self._map.pop(key, None)

    def values(self):
        return self._map.values()

    def __len__(self):
        return len(self._map)

    def items_sorted(self) -> list[tuple[tuple, SerializedTransaction]]:
        return sorted(self._map.items())


class LedgerMaster:
    """Holds the chain: validated ←closed ←current(open)."""

    def __init__(
        self, hash_batch: Optional[Callable] = None, router=None,
        tracer=None,
    ):
        self._lock = threading.RLock()
        self.hash_batch = hash_batch
        # tracing plane: close-stage spans + per-tx splice/fallback marks
        # (consensus rounds built over this chain trace through it too)
        self.tracer = tracer if tracer is not None else get_tracer()
        # HashRouter: close-time re-application consults SF_SIGGOOD so
        # txs verified at submit are not host-re-verified per close
        # (reference: LedgerConsensus::applyTransaction skips checkSign
        # via SF_SIGGOOD, LedgerConsensus.cpp:2101-2106)
        self.router = router
        self.current: Optional[Ledger] = None  # open
        self.closed: Optional[Ledger] = None  # last closed (LCL)
        self.validated: Optional[Ledger] = None
        self.ledger_history: dict[int, bytes] = {}  # seq -> hash
        # closed-ledger cache: bounded + aged so a long-running node's
        # memory does not grow with chain length (reference: LedgerHistory
        # TaggedCache, tuned at Application.cpp:723-727)
        from ..utils.taggedcache import TaggedCache

        self.ledgers_by_hash: TaggedCache = TaggedCache(
            "ledger_history", target_size=512, expiration_s=600.0
        )
        # optional loader for cache misses (Node wires the NodeStore in;
        # overlay validators are memory-resident and leave it unset)
        self.fetch_fallback: Optional[Callable[[bytes], Optional[Ledger]]] = None
        # optional LIGHT resolver: ledger hash -> (seq, parent_hash)
        # from the stored header alone (no tree loads) — used by the
        # LCL-switch reindex walk
        self.header_fetch: Optional[
            Callable[[bytes], Optional[tuple[int, bytes]]]
        ] = None
        # txns held for a future ledger (reference: mHeldTransactions)
        # value is (tx, expire_seq): bounded + expired by ledger seq so
        # a sequence-gap flood cannot pin memory forever
        self.held: dict[tuple[bytes, int], tuple[SerializedTransaction, int]] = {}
        self.held_stats = {"evicted": 0, "expired": 0}
        # admission-control plane ([txq]): wired by Node; promotion of
        # queued txs into each new open ledger happens at _open_next
        self.txq = None
        self.min_validations = 0  # quorum for checkAccept
        self.on_validated: Optional[Callable[[Ledger], None]] = None
        # optional persist-row materializer (Node wires build_tx_rows):
        # when set, the close overlaps this Python tail with the seal
        # tree-hash, whose native/device batches release the GIL
        self.persist_prep: Optional[Callable[[Ledger, dict], list]] = None
        # speculative delta-replay close ([close] delta_replay): the
        # open-ledger accept also runs the tx once in close mode against
        # a SpecView, and the close splices the recorded delta when the
        # read set still validates (engine/deltareplay.py)
        self.delta_replay = True
        # whether the open window speculates at all: decided once a
        # window, where it opens, from the splice shares of the closes
        # made so far (an observation of the traffic, not an option)
        self.spec_policy = SpecPolicy()
        # close-info counters live in one AtomicCounters bundle: the
        # close path, the TxQ's deferred promotion job, and the parallel
        # executor's commit thread all feed close-adjacent counters from
        # their own threads, and bare `dict +=` would lose updates
        self.delta_stats = AtomicCounters(
            "closes", "spliced", "fallback", "invalidated",
            # closes sealed from the pre-hashed building tree, and open
            # ledgers whose building tree a failed fold disarmed (the
            # exception fold_building swallows, as a number)
            "incremental_seals", "building_fold_failures",
            # `fallback` by the reason `try_splice` named
            *(f"fallback.{r}" for r in FALLBACK_REASONS),
            # open windows the policy opened without speculation, and
            # the dry runs not made in them. Everything above counts the
            # closes that consulted records, and those alone
            "windows_skipped", "txs_unspeculated",
        )
        # what the transactors counted in the transactions of closed
        # ledgers (`offers.*`, `flow.*`: engine/offers.py,
        # engine/payment.py), each transaction once: a spliced record's
        # count is its speculation's, a fallback's its serial apply's
        self.engine_stats = AtomicCounters(
            "offers.created", "offers.crossed", "offers.removed_unfunded",
            "offers.cancelled", "offers.replaced", "offers.book_steps",
            "offers.bridged", "flow.payments", "flow.book_steps",
        )
        self.last_close: dict = {}
        # the hot-node cache's (faults, fault_s, evictions) when the
        # last close ended: `close.total` carries the differences
        cache = inner_node_cache()
        self._cache_marks = (cache.faults, cache.fault_s, cache.evictions)
        # THREAD_ROLES.marks() at the end of the last traced close: the
        # close cycle's wall and CPU seconds are differences against it
        self._cycle_marks: Optional[tuple] = None
        # parallel speculative executor ([spec] workers=N, engine/
        # specexec.py): when active, _speculate_open dispatches to the
        # worker pool instead of executing inline, and the close drains
        # the window before consuming the records. None/inactive keeps
        # the serial inline path byte-for-byte.
        self.spec_executor = None
        # incremental O(dirty) seal ([tree] incremental, default on):
        # speculated writes fold into a pre-seal "building" tree on the
        # SpecState, and a background drainer hashes its dirty subtrees
        # through the routed hash plane between closes — the in-close
        # seal then adopts the pre-hashed root and hashes only the
        # residual (engine/deltareplay.py maybe_adopt_prehashed). The
        # full serial seal remains the per-close fallback, never forked.
        self.incremental_seal = True
        self.seal_drain_batch = 256  # writes folded before a drain fires
        self.tree_stats = {
            "drains": 0, "drained_nodes": 0, "seal_adopted": 0,
            "seal_rejected": 0, "seal_residual_keys": 0,
            "bulk_merges": 0, "bulk_merged_keys": 0,
        }
        self._drain_cv = threading.Condition()
        self._drain_pending = 0
        self._drain_kick = False
        self._drain_busy = False
        self._drainer: Optional[threading.Thread] = None
        self._drain_stop = False
        # the per-close stage latencies (apply pass, seal overlap,
        # total) are the tracer's `close.*` spans: ONE histogram an
        # interval, in tracer.stage_hist, which delta_replay_json()
        # reads back

    # -- bootstrap --------------------------------------------------------

    def start_new_ledger(self, root_account_id: bytes, close_time: int = 0) -> None:
        """Fresh genesis chain (reference: Application::startNewLedger —
        builds the seq-1 genesis, closes it, opens seq 2 on top)."""
        with self._lock:
            genesis = Ledger.genesis(root_account_id, close_time=close_time,
                                     hash_batch=self.hash_batch)
            genesis.close(close_time, genesis.close_resolution)
            genesis.accepted = True
            self._push_closed(genesis)
            self.validated = genesis
            self._open_window(genesis)

    def load_ledger(self, ledger: Ledger) -> None:
        """Resume from a stored closed ledger (reference: loadOldLedger)."""
        with self._lock:
            ledger.accepted = True
            self._push_closed(ledger)
            self.validated = ledger
            self._open_window(ledger)

    def _open_window(self, parent: Ledger) -> None:
        """Open `parent`'s successor, and decide whether this window
        speculates: once, here, never in the middle of a window (a
        window has a SpecState, a building tree and records for every
        transaction it accepts, or none of them). Caller holds the
        lock."""
        self.current = parent.open_successor()
        if self.delta_replay and not self.spec_policy.open_window():
            self.delta_stats.add("windows_skipped")

    def _push_closed(self, ledger: Ledger) -> None:
        self.closed = ledger
        h = ledger.hash()
        # the validated chain is AUTHORITATIVE for its index slots: a
        # stale round churning out a late close at an already-validated
        # seq (fork-repair flapping) must not clobber the validated
        # entry — its validation is already refused by can_sign, and
        # the history index must stay the validated truth (scenario-
        # fuzzer find: honest histories permanently disagreed after a
        # partition healed through competing branches)
        floor = self.validated.seq if self.validated is not None else 0
        if ledger.seq > floor or self.ledger_history.get(ledger.seq) is None:
            self.ledger_history[ledger.seq] = h
        if len(self.ledger_history) > 8192:
            # bound the seq index too; full history stays in txdb/nodestore
            del self.ledger_history[min(self.ledger_history)]
        self.ledgers_by_hash.put(h, ledger)

    # -- accessors --------------------------------------------------------

    def current_ledger(self) -> Ledger:
        with self._lock:
            assert self.current is not None, "LedgerMaster not started"
            return self.current

    def closed_ledger(self) -> Ledger:
        with self._lock:
            assert self.closed is not None, "LedgerMaster not started"
            return self.closed

    def get_ledger_by_seq(self, seq: int) -> Optional[Ledger]:
        with self._lock:
            h = self.ledger_history.get(seq)
            return self.ledgers_by_hash.get(h) if h else None

    def get_ledger_by_hash(self, h: bytes) -> Optional[Ledger]:
        with self._lock:
            led = self.ledgers_by_hash.get(h)
            if led is None and self.fetch_fallback is not None:
                led = self.fetch_fallback(h)
                if led is not None:
                    self.ledgers_by_hash.put(h, led)
            return led

    def validated_ledger_at(self, seq: int) -> Optional[Ledger]:
        """The VALIDATED chain's ledger at `seq`, or None: ahead of the
        last validated ledger, or not resolvable. Walked by parent hash
        from the last validated ledger, headers only, for the last 256
        sequences (what `set_validated` repairs); further back the
        index's slot, which only the validated chain writes below its
        floor. A sequence alone can name a ledger this node closed by
        itself and left; a walk from the quorum's tip cannot."""
        with self._lock:
            tip = self.validated
            if tip is None or seq > tip.seq:
                return None
            if seq == tip.seq:
                return tip
            if tip.seq - seq > 256:
                return self.get_ledger_by_seq(seq)
            cur_hash = tip.parent_hash
            for _ in range(tip.seq - seq - 1):
                info = self._resolve_header(cur_hash)
                if info is None:
                    return None
                cur_hash = info[1]
            led = self.get_ledger_by_hash(cur_hash)
            return led if led is not None and led.seq == seq else None

    # -- held transactions (reference: addHeldTransaction) ----------------

    def add_held_transaction(self, tx: SerializedTransaction) -> None:
        with self._lock:
            now = self.closed.seq if self.closed is not None else 0
            self._hold(tx, now + HELD_EXPIRE_LEDGERS)

    def _hold(self, tx: SerializedTransaction, expire_seq: int) -> None:
        """Insert with the pile's cap: a full pile evicts its OLDEST
        entry (insertion order) rather than growing without bound."""
        key = (tx.account, tx.sequence)
        if key in self.held:
            # re-hold after a retry keeps the ORIGINAL horizon — a
            # never-applicable tx must not refresh itself forever
            expire_seq = min(expire_seq, self.held[key][1])
        elif len(self.held) >= HELD_CAP:
            self.held.pop(next(iter(self.held)))
            self.held_stats["evicted"] += 1
        self.held[key] = (tx, expire_seq)

    def _drain_held(self) -> list[tuple[SerializedTransaction, int]]:
        """Take every live (tx, expire_seq) pair, dropping expired
        entries. Caller holds the lock."""
        now = self.closed.seq if self.closed is not None else 0
        entries = list(self.held.values())
        self.held.clear()
        live = []
        for tx, expire in entries:
            if expire < now:
                self.held_stats["expired"] += 1
            else:
                live.append((tx, expire))
        return live

    def take_held_transactions(self) -> list[SerializedTransaction]:
        with self._lock:
            return [tx for tx, _expire in self._drain_held()]

    # -- apply to the open ledger (reference: doTransaction) --------------

    def do_transaction(self, tx: SerializedTransaction, params: TxParams) -> tuple[TER, bool]:
        with self._lock:
            return self._open_apply(tx, params)

    def _open_apply(self, tx: SerializedTransaction, params: TxParams,
                    speculate: bool = True) -> tuple[TER, bool]:
        """Apply to the open ledger; on accept, seed the parsed-tx memo
        and run the speculative close-mode execution. Caller holds the
        lock. `speculate=False` defers the close-mode dry run — the TxQ
        promotion path uses it to keep the (expensive) speculation OFF
        the close window and re-runs it on a deferred job
        (TxQ._drain_deferred_spec -> _speculate_open)."""
        open_ledger = self.current_ledger()
        engine = TransactionEngine(open_ledger)
        with self.tracer.span("open.apply", "apply", txid=tx.txid(),
                              ledger_seq=open_ledger.seq,
                              type=tx.tx_type.name):
            ter, applied = engine.apply_transaction(tx, params)
        if applied:
            # seed the OPEN ledger's parsed-tx memo so the close path
            # reuses this exact object instead of re-parsing the blob
            # (txid is the blob's content hash). Ownership contract: a
            # submitted tx belongs to the node FOREVER — the object
            # escapes into the closed ledger's parsed_txs and is served
            # from history caches — so callers must never mutate it.
            open_ledger.parsed_txs[tx.txid()] = tx
            # speculate only for OPEN-mode accepts: the open window
            # never mutates ledger state, which is the invariant that
            # makes the SpecView's parent reads equal to the state the
            # close will start from (a close-mode apply through this
            # path would break it)
            if speculate and (int(params) & int(TxParams.OPEN_LEDGER)):
                self._speculate_open(open_ledger, tx)
        return ter, applied

    def _speculate_open(self, open_ledger: Ledger,
                        tx: SerializedTransaction,
                        origin: str = "submit") -> None:
        """Close-mode dry run of an open-accepted tx against the open
        window's speculative overlay (engine/deltareplay.py), creating
        the SpecState on first use. `origin` tags the record so the
        queue's promotion counters can tell spliced-promoted txs apart
        from submit-time speculation."""
        if not self.delta_replay:
            return
        if not self.spec_policy.speculating:
            # the closes before this window threw their records away:
            # no SpecState, so the close runs the plain serial apply
            self.delta_stats.add("txs_unspeculated")
            return
        spec = getattr(open_ledger, "_spec_state", None)
        if spec is None:
            from ..engine.deltareplay import SpecState

            spec = open_ledger._spec_state = SpecState(
                open_ledger, tracer=self.tracer)
            if self.incremental_seal:
                # the open window never mutates the state map, so
                # its root IS the parent state the close starts
                # from — the building tree folds speculated
                # writes onto it and pre-hashes between closes
                spec.attach_building(
                    open_ledger.state_map, self.hash_batch
                )
        if tx.txid() in spec.records:
            return
        ex = self.spec_executor
        if ex is not None and ex.active:
            # parallel plane: dispatch to the worker pool (O(1) under
            # the chain lock — the execution itself runs on workers and
            # commits in index order off this thread). Folding into the
            # building tree rides the commit step via _note_fold.
            session = getattr(spec, "_exec_session", None)
            if session is None and ex.can_accept:
                session = spec._exec_session = ex.begin_window(
                    spec, open_ledger, on_fold=self._note_fold,
                )
            if session is not None:
                if ex.dispatch(session, tx, origin):
                    return
                # executor refused (stopping / pool dead): seal the
                # window so no late commit races the serial path, then
                # fall through
                ex.end_window(session, timeout=ex.drain_timeout_s)
                spec._exec_session = None
        with self.tracer.span("open.speculate", "apply",
                              txid=tx.txid(), origin=origin,
                              type=tx.tx_type.name):
            spec.speculate(tx, origin=origin)
        rec = spec.records.get(tx.txid())
        if rec is not None and spec.building is not None:
            folded = spec.fold_building(rec)
            if folded:
                self._note_fold(folded)

    # -- incremental-seal background drain --------------------------------

    def _ensure_drainer_locked(self) -> None:
        """Lazily start the seal-drain thread; caller holds _drain_cv."""
        if self._drainer is None and not self._drain_stop:
            self._drainer = threading.Thread(
                target=THREAD_ROLES.wrap("seal", self._drain_loop),
                name="seal-drain", daemon=True,
            )
            self._drainer.start()

    def _note_fold(self, n_ops: int) -> None:
        """Count folded writes; past the drain batch, wake the drainer to
        pre-hash the building tree's dirty subtrees off this thread.
        drain_batch < 1 disables background drains entirely (folding and
        root adoption still run; the seal just hashes at close time)."""
        if self.seal_drain_batch < 1:
            return
        with self._drain_cv:
            self._drain_pending += n_ops
            if self._drain_pending >= self.seal_drain_batch:
                self._ensure_drainer_locked()
                self._drain_cv.notify()

    def kick_seal_drain(self, wait_s: float = 0.0) -> None:
        """Flush the sub-batch fold residual to the background pre-hash
        thread NOW (the parallel executor's pre-close advisory drain
        lands folds in a burst right before the close — without a kick
        they would sit below the drain-batch threshold and get hashed
        inside the close's lock window instead of outside it). With
        ``wait_s``, block up to that long for the drainer to go idle so
        a caller about to close sees the pre-hash actually finished —
        still outside any lock, and bounded."""
        if self.seal_drain_batch < 1:
            return
        with self._drain_cv:
            if self._drain_pending > 0:
                self._ensure_drainer_locked()
                self._drain_kick = True
                self._drain_cv.notify()
            if wait_s > 0:
                deadline = time.perf_counter() + wait_s
                while (self._drain_pending > 0 or self._drain_kick
                       or self._drain_busy) and not self._drain_stop:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    self._drain_cv.wait(min(remaining, 0.05))

    def _drain_loop(self) -> None:
        from ..state.shamap import compute_hashes

        # the hasher is fixed per LedgerMaster: probe its hash_tree
        # hint capability once, not one inspect.signature per drain
        supports_hint: Optional[bool] = None
        while True:
            with self._drain_cv:
                # max(1, batch): a runtime knob change to <1 must idle
                # the thread (pending only grows via _note_fold, which
                # gates on the same knob), never spin it
                while (self._drain_pending < max(1, self.seal_drain_batch)
                       and not self._drain_kick
                       and not self._drain_stop):
                    self._drain_cv.wait(timeout=1.0)
                if self._drain_stop:
                    return
                todo = self._drain_pending
                self._drain_pending = 0
                self._drain_kick = False
                self._drain_busy = True
            # snapshot the building tree UNDER the chain lock, hash it
            # OUTSIDE: the tree is persistent, so hashing a snapshot
            # root only fills write-once _hash slots on nodes the
            # foreground shares — concurrent folds build new paths and
            # never touch fields this walk writes
            with self._lock:
                cur = self.current
                spec = getattr(cur, "_spec_state", None) if cur else None
                building = spec.building if spec is not None else None
                root = building.root if building is not None else None
                hasher = building.hash_batch if building is not None else None
            if root is None:
                with self._drain_cv:
                    self._drain_busy = False
                    self._drain_cv.notify_all()
                continue
            t0 = time.perf_counter()
            try:
                tree_fn = getattr(hasher, "hash_tree", None)
                if tree_fn is not None \
                        and not getattr(hasher, "fused_enabled", True):
                    tree_fn = None  # [tree] fused=0: staged per-level
                if tree_fn is not None:
                    if supports_hint is None:
                        import inspect

                        supports_hint = (
                            "hint_nodes"
                            in inspect.signature(tree_fn).parameters
                        )
                    if supports_hint:
                        n = tree_fn(root, hint_nodes=todo)
                    else:
                        n = tree_fn(root)
                else:
                    n = compute_hashes(root, hasher)
            except Exception:  # noqa: BLE001 — pre-hashing is advisory;
                # the close's full seal recomputes whatever is missing
                with self._drain_cv:
                    self._drain_busy = False
                    self._drain_cv.notify_all()
                continue
            t1 = time.perf_counter()
            with self._drain_cv:
                self.tree_stats["drains"] += 1
                self.tree_stats["drained_nodes"] += n
                self._drain_busy = False
                self._drain_cv.notify_all()
            self.tracer.complete("seal.incremental", "seal", t0, t1,
                                 nodes=n)

    def stop_seal_drainer(self) -> None:
        """Stop the background pre-hash thread (Node.stop). Idempotent;
        a stopped LedgerMaster never restarts it."""
        with self._drain_cv:
            self._drain_stop = True
            self._drain_cv.notify_all()
        t = self._drainer
        if t is not None:
            t.join(timeout=5)

    def engine_json(self) -> dict:
        """The transactors' counters, by block: ``{"offers": {...},
        "flow": {...}}`` for get_counts."""
        out: dict = {}
        for name, n in self.engine_stats.snapshot().items():
            block, _dot, key = name.partition(".")
            out.setdefault(block, {})[key] = n
        return out

    def tree_json(self) -> dict:
        """Batched-commit-plane counters for get_counts/server_state."""
        with self._drain_cv:
            out = dict(self.tree_stats)
        out["incremental_seal"] = self.incremental_seal
        out["drain_batch"] = self.seal_drain_batch
        # drain latency from the tracer's `seal.incremental` stage
        # histogram (absent with `[trace] enabled=0`)
        hist = self.tracer.stages("seal.", ("incremental",)).get(
            "incremental")
        if hist is not None and hist.count:
            out["drain_p50_ms"] = hist.quantile(0.5)
            out["drain_p90_ms"] = hist.quantile(0.9)
        return out

    # -- close (standalone / consensus-accept share this tail) ------------

    def _parse_with_verdict(self, open_ledger: Ledger, txid: bytes, blob: bytes):
        """Parse an open-ledger blob — or reuse the submit-time parsed
        object from the ledger's own memo (txid is content-addressed,
        so a hit is byte-equal) — carrying over the submit-time
        SF_SIGGOOD verdict so close/re-apply never host-re-verifies
        (reference: LedgerConsensus::applyTransaction skips checkSign
        via SF_SIGGOOD, LedgerConsensus.cpp:2101-2106)."""
        tx = open_ledger.parse_tx(txid, blob)
        if self.router is not None and (
            self.router.get_flags(txid) & SF_SIGGOOD
        ):
            tx.set_sig_verdict(True)
        return tx

    def _seal(self, new_lcl: Ledger, results: dict[bytes, TER]) -> None:
        """Shared seal tail of both close paths: compute the two tree
        hashes while the persist-row materialization runs.

        The tree hashes are the close's crypto block — their batches run
        in the GIL-releasing native/device hashers when configured — so
        the tx map and the state map each hash on their OWN helper
        thread (the two trees are disjoint, and the device hasher's
        routing model is thread-safe, so the two fused chains overlap on
        the mesh) while THIS thread does the pure-Python persist tail
        (meta parse, affected-account walk, row build). The SHAMap is
        persistent: hashing only fills node._hash slots, and the row
        walk reads item data/children, so the traversals never write the
        same fields. A hashing failure on a helper thread is absorbed —
        _push_closed recomputes serially.

        Emits the transfer-honesty spans: ``close.device.fused`` (the
        overlapped hash window + whether the fused whole-tree pipeline
        was eligible) and ``close.device.transfer`` (per-close deltas of
        the hash plane's TransferMeter — the device-residency proof)."""
        if self.persist_prep is None:
            return
        t0 = time.perf_counter()
        tj = getattr(self.hash_batch, "transfer_json", None)
        before = tj() if tj is not None else None

        done = threading.Event()
        pending = [2]
        plock = threading.Lock()

        traced = self.tracer.enabled

        def _arm(get_hash):
            def run():
                if traced:  # two threads a close: no clock read untraced
                    THREAD_ROLES.enter("seal")
                try:
                    get_hash()
                except Exception:  # noqa: BLE001 — recomputed on push
                    pass
                finally:
                    THREAD_ROLES.leave()
                    with plock:
                        pending[0] -= 1
                        if pending[0] == 0:
                            done.set()
            return run

        threads = [
            threading.Thread(target=_arm(new_lcl.tx_map.get_hash),
                             name="seal-hash-tx"),
            threading.Thread(target=_arm(new_lcl.state_map.get_hash),
                             name="seal-hash-state"),
        ]
        for t in threads:
            t.start()
        try:
            new_lcl.persist_rows = self.persist_prep(new_lcl, results)
        except Exception:  # noqa: BLE001 — the persist stage rebuilds rows
            pass
        finally:
            done.wait()
            for t in threads:
                t.join()
        t1 = time.perf_counter()
        self.tracer.complete(
            "close.device.fused", "seal", t0, t1,
            fused=bool(getattr(self.hash_batch, "fused_enabled", True)),
            seq=new_lcl.seq,
        )
        if before is not None:
            after = tj()
            if after is not None:
                self.tracer.complete(
                    "close.device.transfer", "seal", t0, t1,
                    seq=new_lcl.seq,
                    uploads=after["uploads"] - before["uploads"],
                    readbacks=after["readbacks"] - before["readbacks"],
                    transfers=after["transfers"] - before["transfers"],
                    bytes_moved=(after["bytes_moved"]
                                 - before["bytes_moved"]),
                )

    def close_and_advance(
        self,
        close_time: int,
        close_resolution: int,
        correct_close_time: bool = True,
        extra_txs: Optional[list[SerializedTransaction]] = None,
    ) -> tuple[Ledger, dict[bytes, TER]]:
        """Build the next closed ledger from the open ledger's tx set and
        advance the chain. This is the shared tail of the reference's
        LedgerConsensus::accept (:931-1127) and the standalone
        `ledger_accept` path (NetworkOPs::acceptLedger):

        1. collect the open ledger's txns (+ any consensus extras) into a
           CanonicalTXSet salted by the parent hash,
        2. re-apply them to a successor of the LCL with retry passes
           (applyTransactions, LedgerConsensus.cpp:1935-2070),
        3. seal it, open the next ledger, re-apply held txns.

        Returns (new closed ledger, per-txid results).
        """
        with self._lock:
            t0 = time.perf_counter()
            c0 = self.tracer.thread_cpu()
            prev = self.closed_ledger()
            open_ledger = self.current_ledger()

            # 1. canonical set from the open ledger's recorded blobs;
            # SF_SIGGOOD verdicts memoized at submit time carry over to
            # the freshly-parsed copies (the reference's close path
            # skips checkSign the same way)
            txset = CanonicalTXSet(prev.hash())
            for txid, blob, _meta in open_ledger.tx_entries():
                txset.insert(self._parse_with_verdict(open_ledger, txid, blob))
            for tx in extra_txs or []:
                txset.insert(tx)

            # 2. successor of the LCL; apply with retry passes, splicing
            # speculative deltas where the open pass's records validate
            new_lcl = prev.open_successor()
            spec = (
                getattr(open_ledger, "_spec_state", None)
                if self.delta_replay else None
            )
            self._drain_spec(spec)
            results = self._apply_transactions(new_lcl, txset, spec=spec)
            t_apply = time.perf_counter()
            c_apply = self.tracer.thread_cpu()

            # 3. seal + advance
            new_lcl.close(close_time, close_resolution, correct_close_time)
            new_lcl.accepted = True
            # seed the parsed-tx memo so persist/publish reuse these
            # exact objects instead of re-parsing every blob
            for tx in txset.values():
                new_lcl.parsed_txs[tx.txid()] = tx
            # overlap: tree-hash (GIL-releasing crypto batches) on a
            # helper thread while the persist rows materialize here
            self._seal(new_lcl, results)
            t_seal = time.perf_counter()
            c_seal = self.tracer.thread_cpu()
            self._push_closed(new_lcl)
            self._open_next(new_lcl, (t_apply - t0) * 1000.0)

            # standalone trusts its own closes (reference: standalone mode
            # skips validations; checkAccept quorum handles the net case)
            if self.min_validations == 0:
                self.validated = new_lcl
                if self.on_validated:
                    self.on_validated(new_lcl)

            self._note_close_stages(t0, t_apply, t_seal, new_lcl.seq,
                                    (c0, c_apply, c_seal))
            return new_lcl, results

    def close_with_txset(
        self,
        txs: list[SerializedTransaction],
        close_time: int,
        close_resolution: int,
        correct_close_time: bool = True,
    ) -> tuple[Ledger, dict[bytes, TER]]:
        """Consensus-accept path (reference: LedgerConsensus::accept,
        :931-1127): close the chain with the *agreed* tx set — which may
        differ from our open ledger's — then re-apply to the new open
        ledger anything we had locally that didn't make the consensus set
        (reference: reapply of local/disputed txns :1050-1127)."""
        with self._lock:
            t0 = time.perf_counter()
            c0 = self.tracer.thread_cpu()
            prev = self.closed_ledger()
            open_ledger = self.current_ledger()

            txset = CanonicalTXSet(prev.hash())
            for tx in txs:
                txset.insert(tx)

            new_lcl = prev.open_successor()
            spec = (
                getattr(open_ledger, "_spec_state", None)
                if self.delta_replay else None
            )
            self._drain_spec(spec)
            results = self._apply_transactions(new_lcl, txset, spec=spec)
            t_apply = time.perf_counter()
            c_apply = self.tracer.thread_cpu()

            new_lcl.close(close_time, close_resolution, correct_close_time)
            new_lcl.accepted = True
            for tx in txset.values():
                new_lcl.parsed_txs[tx.txid()] = tx
            self._seal(new_lcl, results)
            t_seal = time.perf_counter()
            c_seal = self.tracer.thread_cpu()
            self._push_closed(new_lcl)

            # re-apply: our open-ledger txns that missed consensus first
            # (they are the lower sequences), then held/queued;
            # SF_SIGGOOD verdicts from submit time carry over so the
            # re-apply never host-re-verifies
            consensus_ids = {tx.txid() for tx in txs}
            leftovers = [
                self._parse_with_verdict(open_ledger, txid, blob)
                for txid, blob, _meta in open_ledger.tx_entries()
                if txid not in consensus_ids
            ]
            # the tx map hands them over in txid order: put an account's
            # back in sequence, or the later one meets the new open
            # ledger first, is held as terPRE_SEQ, and every submission
            # of that account behind it queues up behind the hold
            leftovers.sort(key=lambda tx: (tx.account, tx.sequence))
            self._open_next(new_lcl, (t_apply - t0) * 1000.0,
                            leftovers=leftovers)
            self._note_close_stages(t0, t_apply, t_seal, new_lcl.seq,
                                    (c0, c_apply, c_seal))
            return new_lcl, results

    def _open_next(self, new_lcl: Ledger, apply_ms: float,
                   leftovers: list = ()) -> None:
        """Open the successor ledger and replenish it: consensus
        leftovers first, then the held pile / admission queue. With the
        TxQ enabled this is the promotion site — held terPRE_SEQ txs are
        absorbed into the fee-ordered queue and the best-paying eligible
        queued txs fill the new open ledger up to the soft cap (the
        [txq] enabled=0 kill-switch keeps the legacy held re-apply path
        byte-for-byte). Caller holds the lock."""
        self._retire_open()
        self._open_window(new_lcl)
        for tx in leftovers:
            ter, _applied = self._open_apply(
                tx, TxParams.OPEN_LEDGER | TxParams.RETRY
            )
            if ter == TER.terPRE_SEQ:
                self._hold_or_queue(tx)
        txq = self.txq
        if txq is not None and txq.enabled:
            # fold any held entries (validator/networked submit path
            # still feeds the pile directly) into the queue, then
            # promote; capacity model feeds from this close's apply pass
            for tx, expire in self._drain_held():
                txq.absorb_held(tx, self, expire)
            txq.after_close(self, new_lcl, apply_ms)
        else:
            for tx, expire in self._drain_held():
                ter, _applied = self._open_apply(
                    tx, TxParams.OPEN_LEDGER | TxParams.RETRY
                )
                if ter == TER.terPRE_SEQ:
                    self._hold(tx, expire)

    def _retire_open(self) -> None:
        """The open ledger is about to be replaced: take its
        speculation state off it. The state's view points back at the
        ledger, a cycle that held every record of the window (and the
        building tree) until the collector's next walk of the old
        generation; cut here, the window's records die with the close
        that consumed them, by reference count. Caller holds the lock."""
        old = self.current
        if old is not None and getattr(old, "_spec_state", None) is not None:
            old._spec_state = None

    def _drain_spec(self, spec) -> None:
        """Seal the open window's parallel-speculation session before
        the close consumes its records: every dispatched task commits
        (in-flight work finishes through the pool; a wedged pool's
        remainder is executed serially in index order on this thread —
        the close-side fallback batch also drains through the executor).
        No-op on the serial path. Caller holds the chain lock; the
        commit machinery never takes it, so waiting here cannot
        deadlock."""
        ex = self.spec_executor
        session = getattr(spec, "_exec_session", None) if spec else None
        if ex is None or session is None:
            return
        t0 = time.perf_counter()
        ex.end_window(session)
        spec._exec_session = None
        self.tracer.complete("spec.drain", "close", t0,
                             time.perf_counter(),
                             dispatched=len(session.tasks))

    def _hold_or_queue(self, tx: SerializedTransaction) -> None:
        """terPRE_SEQ disposition: the fee-ordered queue when the TxQ is
        enabled, the (bounded) held pile otherwise."""
        if self.txq is not None and self.txq.enabled:
            self.txq.absorb_held(tx, self)
        else:
            self.add_held_transaction(tx)

    def switch_lcl(self, ledger: Ledger) -> None:
        """Adopt a different (acquired) last-closed ledger — the network
        moved on without us (reference: switchLastClosedLedger,
        NetworkOPs.cpp:930). Our open-ledger txns are NOT carried over;
        anything still valid will be re-relayed by peers."""
        with self._lock:
            ledger.accepted = True
            self._push_closed(ledger)
            self._retire_open()
            self._open_window(ledger)
            self._reindex_chain(ledger)

    def _reindex_chain(self, ledger: Ledger) -> None:
        """Repoint the seq->hash index at the adopted chain's ancestry.
        Closes we made ourselves before the switch are ORPHANS: leaving
        them indexed would make get_ledger_by_seq (and the `ledger` RPC)
        serve a ledger the network never validated at that index — the
        mismatch the reference's LedgerHistory::handleMismatch repairs.
        Repoints every resolvable ancestor; index entries between the
        last VALIDATED seq and the deepest confirmed ancestor that
        cannot be confirmed are DROPPED — after a switch they are
        orphan-branch closes, and serving nothing (the caller falls
        back to stored history, whose own divergence is LedgerCleaner
        repair territory) beats serving a ledger the network never
        validated. The tip itself was just indexed by _push_closed; the
        walk starts at its parent. Ancestry resolves from the in-memory
        cache or the LIGHT header fetch (seq + parent only) — never a
        full two-tree Ledger.load under the master lock — and stops at
        the validated floor, which no switch may rewrite."""
        floor = self.validated.seq if self.validated is not None else 0
        resolve = self._resolve_header
        cur_hash = ledger.parent_hash
        confirmed_down_to = ledger.seq
        while True:
            info = resolve(cur_hash)
            if info is None:
                break
            seq, parent_hash = info
            if seq <= floor:
                break  # never rewrite the validated chain's entries
            if self.ledger_history.get(seq) == cur_hash:
                confirmed_down_to = seq
                break
            self.ledger_history[seq] = cur_hash
            confirmed_down_to = seq
            cur_hash = parent_hash
        # one pass: (a) unconfirmable entries between the floor and the
        # deepest confirmed ancestor are orphan-branch closes; (b)
        # entries ABOVE the adopted tip are our own solo closes on an
        # abandoned fork (backward adoption repairs a runaway node) —
        # the network validated neither
        for seq in [
            s for s in self.ledger_history
            if floor < s < confirmed_down_to or s > ledger.seq
        ]:
            del self.ledger_history[seq]
        while len(self.ledger_history) > 8192:
            del self.ledger_history[min(self.ledger_history)]

    def _resolve_header(self, h: bytes) -> Optional[tuple[int, bytes]]:
        """(seq, parent_hash) for a ledger hash, from the in-memory
        cache or the LIGHT header fetch — never a full two-tree load
        under the master lock."""
        led = self.ledgers_by_hash.get(h)
        if led is not None:
            return led.seq, led.parent_hash
        if self.header_fetch is not None:
            return self.header_fetch(h)
        return None

    def set_validated(self, ledger: Ledger) -> None:
        """A quorum of trusted validations arrived for this ledger
        (reference: LedgerMaster::checkAccept tail, :705-750)."""
        with self._lock:
            if self.validated is not None and ledger.seq <= self.validated.seq:
                return
            prev_floor = (
                self.validated.seq if self.validated is not None else 0
            )
            self.validated = ledger
            # a quorum-validated ledger is the strongest possible signal
            # for its index slot: repair any orphan entry left by a fork
            # healed without an LCL switch (LedgerHistory mismatch role)
            self.ledger_history[ledger.seq] = ledger.hash()
            self.ledgers_by_hash.put(ledger.hash(), ledger)
            # and for every slot it SKIPPED: when validation jumps a
            # seq range (contested rounds, a revived node), the new
            # tip's ancestry is authoritative for the gap — without
            # this, a node that closed an orphan inside the gap served
            # that orphan from its history forever (scenario-fuzzer
            # find: honest histories permanently disagreed at a seq
            # below the validated floor)
            # bounded: never walk (or grow the index) past the 8192
            # history bound — a cold node whose first validation lands
            # at a high seq must not do seq-many header reads under
            # the master lock
            prev_floor = max(prev_floor, ledger.seq - 256)
            cur_hash = ledger.parent_hash
            seq = ledger.seq - 1
            while seq > prev_floor:
                self.ledger_history[seq] = cur_hash
                info = self._resolve_header(cur_hash)
                if info is None:
                    # deeper ancestry unresolvable from memory/headers:
                    # any remaining gap entries are unconfirmable —
                    # probably this node's own orphan-branch closes from
                    # before the jump. Same policy as the switch_lcl
                    # repair: serving NOTHING beats serving a hash the
                    # network never validated (re-resolvable later via
                    # stored history / LedgerCleaner).
                    for s in range(prev_floor + 1, seq):
                        self.ledger_history.pop(s, None)
                    break
                _seq, cur_hash = info
                seq -= 1
            while len(self.ledger_history) > 8192:
                del self.ledger_history[min(self.ledger_history)]
        if self.on_validated:
            self.on_validated(ledger)

    def check_accept(self, ledger_hash: bytes, trusted_count: int) -> bool:
        """Quorum test for a closed ledger we know about (reference:
        checkAccept) — promotes it to validated when `trusted_count`
        meets `min_validations`."""
        if trusted_count < max(self.min_validations, 1):
            return False
        ledger = self.get_ledger_by_hash(ledger_hash)
        if ledger is None:
            return False
        self.set_validated(ledger)
        return True

    def _apply_transactions(
        self, ledger: Ledger, txset: CanonicalTXSet, spec=None
    ) -> dict[bytes, TER]:
        """reference: LedgerConsensus::applyTransactions — passes over the
        canonical set, retrying ter* failures (which may succeed once an
        earlier tx lands), claiming fees on tec*.

        With a SpecState from the open pass, each tx first consults the
        delta-replay context: a record whose read set validates against
        the close's writer map is spliced (recorded delta + meta, no
        transactor run); everything else runs the full serial apply and
        poisons its written keys (engine/deltareplay.py)."""
        results: dict[bytes, TER] = {}
        tracer = self.tracer
        engine = TransactionEngine(ledger, tracer=tracer)
        # what the transactors counted (`offers.*`, `flow.*`), of the
        # applications that land in this ledger: the serial ones here,
        # the spliced records' in the replay
        tally: dict[str, int] = {}
        replay = None
        if spec is not None and self.delta_replay:
            from ..engine.deltareplay import CloseReplay

            replay = CloseReplay(spec, ledger, tracer=tracer)

        def apply_one(key_tx, final: bool):
            tx = key_tx[1]
            if replay is not None:
                hit = replay.try_splice(engine, tx, final)
                if hit is not None:
                    return hit
                # the serial transactor reads the real trees: queued
                # spliced writes must land first
                replay.flush_pending()
            ter, did_apply = engine.apply_transaction(
                tx, TxParams.NONE if final else TxParams.RETRY
            )
            if did_apply:
                merge_tally(tally, engine.tally)
            if replay is not None:
                replay.note_fallback(tx, engine, did_apply)
            elif tracer.enabled and tracer.sampled(tx.txid()):
                # serial close path (delta replay off / no spec): the
                # per-tx close mark still lands in the causal tree
                tracer.instant("close.tx", "close", txid=tx.txid(),
                               mode="serial", ledger_seq=ledger.seq,
                               ter=int(ter), type=tx.tx_type.name)
            return ter, did_apply

        remaining = txset.items_sorted()
        for pass_no in range(LEDGER_TOTAL_PASSES):
            final_pass = pass_no == LEDGER_TOTAL_PASSES - 1
            retry: list = []
            changes = 0
            for key, tx in remaining:
                ter, did_apply = apply_one((key, tx), final_pass)
                results[tx.txid()] = ter
                if did_apply or ter == TER.tesSUCCESS:
                    changes += 1
                elif -99 <= int(ter) < 0 and not final_pass:  # ter* retry band
                    retry.append((key, tx))
                elif 100 <= int(ter) < 200 and not did_apply and not final_pass:
                    retry.append((key, tx))  # tec w/o fee claim under RETRY
            remaining = retry
            if not remaining or changes == 0:
                # no progress → another pass can't help (final pass already
                # recorded non-retry results)
                if remaining and not final_pass:
                    for key, tx in remaining:
                        ter, _ = apply_one((key, tx), True)
                        results[tx.txid()] = ter
                break
        # `close.apply` carries both: the transactions this close
        # applied, and how many of them had a record to consult
        self.last_close = {"txs": len(results), "speculated": 0}
        if replay is not None:
            replay.flush_pending()
            if self.incremental_seal:
                # adopt the pre-hashed building root where it matches the
                # close's final write set — the seal then hashes only the
                # residual (full seal stays the automatic fallback)
                replay.maybe_adopt_prehashed()
            self.last_close["speculated"] = len(
                results.keys() & spec.records.keys())
            self._note_delta_stats(replay)
            merge_tally(tally, replay.tally)
        if tally:
            self.engine_stats.add_many(**tally)
        return results

    # -- delta-replay / close-stage observability -------------------------

    def _note_delta_stats(self, replay) -> None:
        c = replay.counts()
        self.spec_policy.note_close(c["spliced"], c["consulted"])
        if self.txq is not None and self.txq.enabled:
            # queue-aware speculation honesty: which of the txs the
            # queue promoted into this window spliced vs fell back
            self.txq.note_close_classes(replay.classes())
        # one atomic multi-key bump: concurrent readers (RPC threads,
        # the metrics collector) never see a torn closes/spliced pair
        self.delta_stats.add_many(
            closes=1, spliced=c["spliced"], fallback=c["fallback"],
            invalidated=c["invalidated"],
            incremental_seals=int(c.get("seal_adopt") == "adopted"),
            building_fold_failures=c.get("fold_failures", 0),
            **{f"fallback.{r}": n
               for r, n in c["fallback_by_reason"].items()},
        )
        with self._drain_cv:
            self.tree_stats["bulk_merges"] += c.get("bulk_merges", 0)
            self.tree_stats["bulk_merged_keys"] += c.get(
                "bulk_merged_keys", 0
            )
            adopt = c.get("seal_adopt")
            if adopt == "adopted":
                self.tree_stats["seal_adopted"] += 1
                self.tree_stats["seal_residual_keys"] += c.get(
                    "seal_residual", 0
                )
            elif adopt in ("rejected", "error"):
                self.tree_stats["seal_rejected"] += 1
        self.last_close.update(c)

    def _note_close_stages(self, t0: float, t_apply: float,
                           t_seal: float, seq: int,
                           cpu: tuple = (None, None, None)) -> None:
        now = time.perf_counter()
        c0, c_apply, c_seal = cpu
        # None: no thread clock was read (the tracer is disabled), and
        # the spans carry no cpu_us
        cpu_total = self.tracer.cpu_since(c0)
        stages = {
            "apply_ms": round((t_apply - t0) * 1000.0, 3),
            "seal_ms": round((t_seal - t_apply) * 1000.0, 3),
            "total_ms": round((now - t0) * 1000.0, 3),
        }
        self.last_close.update(stages)
        tr = self.tracer
        tr.complete("close.apply", "close", t0, t_apply, seq=seq,
                    cpu_s=tr.cpu_since(c0, c_apply),
                    txs=self.last_close["txs"],
                    speculated=self.last_close["speculated"])
        tr.complete("close.seal", "close", t_apply, t_seal, seq=seq,
                    cpu_s=tr.cpu_since(c_apply, c_seal))
        cycle = {} if cpu_total is None else self._note_cycle(cpu_total)
        # what the hot-node cache did over this close CYCLE (since the
        # last close ended: the open window's faults are the cycle's),
        # as `replay.span` carries its `evict_scan_s`; over a run the
        # differences sum to the cache's own counters
        cache = inner_node_cache()
        marks = (cache.faults, cache.fault_s, cache.evictions)
        was, self._cache_marks = self._cache_marks, marks
        tr.complete("close.total", "close", t0, now, seq=seq,
                    cpu_s=cpu_total,
                    faults=marks[0] - was[0],
                    fault_s=round(marks[1] - was[1], 6),
                    evictions=marks[2] - was[2],
                    resident_bytes=cache.resident_bytes, **cycle)

    def _note_cycle(self, cpu_close_s: float) -> dict:
        """Who had the interpreter over this close CYCLE (since the last
        close ended): ``cycle_s`` of wall, ``process_cpu_s``, and the
        CPU seconds of the node's threads by role, ``cpu_<role>_s``, as
        differences of ``THREAD_ROLES`` (a dozen clock reads, one a
        thread). ``cpu_other_s`` is the process's clock less every role
        and less this close, unless the closing thread is itself in a
        role (``closer``: a networked node closes on a `net` thread),
        where the close is a part of that role's seconds already. The
        first traced close only sets the marks."""
        marks = THREAD_ROLES.marks()
        was, self._cycle_marks = self._cycle_marks, marks
        if was is None:
            return {}
        out = {"cycle_s": round(marks[0] - was[0], 6),
               "process_cpu_s": round(marks[2] - was[2], 6)}
        other = marks[2] - was[2]
        for role, now_s, was_s in zip(ROLES, marks[1], was[1]):
            out[f"cpu_{role}_s"] = round(now_s - was_s, 6)
            other -= now_s - was_s
        closer = THREAD_ROLES.role_of(threading.get_ident())
        if closer is None:
            other -= cpu_close_s
        else:
            out["closer"] = closer
        out["cpu_other_s"] = round(other, 6)
        return out

    def delta_replay_json(self) -> dict:
        """spliced/fallback/invalidation counters + close-stage latency
        percentiles, for server_state / get_counts. Snapshots under the
        chain lock: RPC worker threads call this while the close thread
        records stages / merges last_close."""
        with self._lock:
            stats = self.delta_stats.snapshot()
            out = {
                "enabled": self.delta_replay,
                **{k: v for k, v in stats.items()
                   if not k.startswith("fallback.")},
                "fallback_by_reason": {
                    r: stats[f"fallback.{r}"] for r in FALLBACK_REASONS},
                "policy": self.spec_policy.get_json(),
                "last_close": dict(self.last_close),
            }
            # close-stage percentiles from the tracer's `close.*` stage
            # histograms (absent with `[trace] enabled=0`)
            for stage, hist in self.tracer.stages(
                    "close.", ("apply", "seal", "total")).items():
                if hist.count:
                    out[f"{stage}_p50_ms"] = hist.quantile(0.5)
                    out[f"{stage}_p90_ms"] = hist.quantile(0.9)
        if self.spec_executor is not None:
            out["spec"] = self.spec_executor.get_json()
        return out
