"""Traffic kind ``quorum``: a validator in its quorum. The measured
validator is set up and served through ``Node`` in this process, as
``drivers/flood.py`` sets up its node, and holds the chip; its peers
are child processes pinned to ``JAX_PLATFORMS=cpu``, each ``python -m
stellard_tpu --conf <ini>`` on its own copy of the same first ledger
(``yardstick/prepared_quorum.py``). The flood's pre-signed payments go
as ``submit`` with ``tx_blob`` to the PEERS' doors only, from one
``loopgen.py`` process a peer in a closed loop; the measured validator
gets every transaction by relay, and every ledger closes by consensus.
The loop has no think time: what paces it is the peers' doors, which
hold a submit while their open ledger has no room (the program's hold
at the ``[txq]`` soft cap of the peers' INI).

Parameters (the traffic file): the stream's (``senders``,
``amount_drops``, ``zipf_theta``, ``planted_per_1024``, ``fee_drops``,
``presign_tx_per_s``, ``warmup_presign_s``: the seconds of stream signed
for the warm-up), the loop's (``connections_per_peer``), the schedule's
(``warmup_rounds`` validated rounds that carry transactions before the
window, ``settle_rounds`` after the generators stop, in which what a
door acknowledged may still be validated, ``mesh_timeout_s``,
``warmup_timeout_s``) and the
checks' (``account_sample``, ``tx_sample``, ``reclose_ledgers``,
``device_check_sigs``).

The window starts when the measured validator sees a ledger validated
and ends, behind the first ledger it sees validated after
``--seconds``, when ``close_pipeline.flush`` has returned. A payment
counts when it is ``tesSUCCESS`` in a ledger the measured validator saw
validated inside the window, read from the ledgers' contents over the
measured validator's door, not from what a door answered.

``correct`` holds the net to the configuration's guarantees, outside
the window: ONE hash a validated sequence over the four doors, the
measured validator no more than a round behind; 3 trusted validations,
its own among them, for every ledger that counted; counted transactions
read back by ``tx`` with ``validated: true`` from the measured validator
and two peers, ``account_info`` against the benchmark's own arithmetic
over the validated ledgers; no planted signature in a validated ledger;
the device-path check of ``standalone-fsync``; and, the net stopped,
sampled window ledgers re-closed on the plain path from the measured
validator's disk and from a peer's.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import threading
import time

from yardstick import nodedrive, prepared_quorum, stats, workload
from yardstick.capture import WINDOW

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
MEASURED = 0  # the validator that runs in this process
POLL_S = 0.01
# A validator child is `python -m stellard_tpu --conf <ini>` behind two
# lines of its own: it asks the kernel for SIGKILL when the driver's
# process dies, however it dies (PR_SET_PDEATHSIG, set by the child on
# itself behind the exec: the driver's process has JAX's threads, so it
# runs no code between fork and exec). A stray validator on a port would
# poison the next run.
VALIDATOR = (
    "import ctypes, os, runpy, signal, sys\n"
    "ctypes.CDLL(None).prctl(1, signal.SIGKILL)\n"
    "if os.getppid() != int(sys.argv[1]): sys.exit(3)\n"
    "sys.argv = ['stellard_tpu', '--conf', sys.argv[2]]\n"
    "runpy.run_module('stellard_tpu', run_name='__main__')\n"
)


class Children:
    """The processes a run starts, in the driver's own process group
    (whoever signals the group reaches them): none outlives the run."""

    def __init__(self):
        self.procs: list[subprocess.Popen] = []

    def spawn(self, argv: list, log_path: str, **kw) -> subprocess.Popen:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        log = open(log_path, "w")
        try:
            proc = subprocess.Popen(
                argv, cwd=REPO, env=env, stdout=kw.pop("stdout", log),
                stderr=log, **kw)
        finally:
            log.close()
        self.procs.append(proc)
        return proc

    def stop(self, grace: float = 20.0) -> None:
        procs = self.procs
        for p in procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.monotonic() + grace
        for p in procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                pass
            if p.poll() is None:
                p.kill()
                p.wait()


def rpc(port: int, method: str, params: dict | None = None,
        timeout: float = 60.0) -> dict:
    return nodedrive.rpc(port, method, params or {}, timeout=timeout)


def program_counters(node) -> dict:
    """What the program counts of the deployment's own work, as it
    stands now: delta replay's splices and fallbacks, what the net
    handed the verify plane (``relay.*``, ``netverify.*``) and what
    crossed the wire (``overlay.*``). A counter the program under test
    lacks is left out, and the metric that reads it finds nothing."""
    dj = node.ledger_master.delta_replay_json()
    out = {f"replay.{k}": dj[k] for k in ("spliced", "fallback") if k in dj}
    for reason, n in (dj.get("fallback_by_reason") or {}).items():
        out[f"replay.fallback.{reason}"] = n
    out.update({f"seal.{k}": dj[k] for k in ("closes", "incremental_seals")
                if k in dj})
    vn = node.overlay.node
    relay = getattr(vn, "relay_stats", None)
    if relay is not None:
        out.update({f"relay.{k}": v for k, v in relay.snapshot().items()})
    netverify_json = getattr(vn, "netverify_json", None)
    if netverify_json is not None:
        nv = netverify_json()
        out["netverify.batches"], out["netverify.sigs"] = (
            nv["batches"], nv["sigs"])
    traffic_json = getattr(node.overlay, "traffic_json", None)
    if traffic_json is not None:
        tj = traffic_json()
        out["overlay.msgs"] = tj["msgs_in_total"] + tj["msgs_out_total"]
        out["overlay.bytes"] = tj["bytes_in_total"] + tj["bytes_out_total"]
        out["overlay.sendq_dropped"] = tj["sendq_dropped"]
    return out


class ValidatedWatch:
    """The ledgers the measured validator sees validated, as it sees
    them: a thread reads ``ledger_master.validated`` every 10 ms and
    notes each new one with the time (the read is a bare attribute
    load; over a run it costs the interpreter well under a thousandth
    of its time)."""

    def __init__(self, node, on_new=None):
        self.node = node
        self.seen: list[tuple[int, bytes, int, float]] = []  # seq hash txs t
        self.on_new = on_new
        self._stop = threading.Event()
        self._cv = threading.Condition()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="validated-watch")
        self._thread.start()

    def _run(self) -> None:
        last = None
        drained = time.perf_counter()
        while not self._stop.wait(POLL_S):
            led = self.node.ledger_master.validated
            if led is None or led is last:
                # the span ring holds 16,384 events: drain it every
                # two seconds too, so a long round cannot wrap it
                if (self.on_new is not None
                        and time.perf_counter() - drained > 2.0):
                    drained = time.perf_counter()
                    self.on_new(None)
                continue
            last = led
            results = getattr(led, "apply_results", None)
            txs = len(results) if results is not None else len(led.tx_map)
            row = (led.seq, led.hash(), txs, time.perf_counter())
            with self._cv:
                self.seen.append(row)
                self._cv.notify_all()
            if self.on_new is not None:
                self.on_new(row)

    def wait_for(self, pred, timeout: float):
        """-> the first noted ledger (from now on, or already there)
        that ``pred`` accepts, or None at the timeout."""
        deadline = time.monotonic() + timeout
        checked = 0
        with self._cv:
            while True:
                for row in self.seen[checked:]:
                    if pred(row):
                        return row
                checked = len(self.seen)
                left = deadline - time.monotonic()
                if left <= 0:
                    return None
                self._cv.wait(timeout=min(left, 1.0))

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)


class Net:
    """The four validators of a run and the generators that load the
    three peers."""

    def __init__(self, ctx, children: Children):
        self.ctx = ctx
        self.children = children
        cfg = ctx.config
        self.n = int(cfg["net"]["validators"])
        self.keys = prepared_quorum.validator_keys(cfg)
        with open(os.path.join(BENCH, "configs", cfg["peer_ini"])) as fh:
            self.peer_template = fh.read()
        for old, new in cfg.get("ini_replace", {}).items():
            self.peer_template = self.peer_template.replace(old, new)
        from stellard_tpu.testkit.tcpnet import free_ports

        ports = free_ports(self.n + 2 * (self.n - 1))
        self.peer_ports = ports[:self.n]
        self.rpc_ports = [None] + ports[self.n:2 * self.n - 1]
        self.ws_ports = [None] + ports[2 * self.n - 1:]
        self.inis: dict[int, str] = {}
        self.peers: dict[int, subprocess.Popen] = {}
        self.node = None
        self.gens: dict[int, subprocess.Popen] = {}
        self.gen_results: dict[int, str] = {}

    def _workdir(self, i: int, prepared_dir: str) -> tuple[str, dict]:
        workdir = os.path.join(self.ctx.work_root, f"validator-{i}")
        os.makedirs(workdir)
        meta = prepared_quorum.copy_for(prepared_dir, workdir)
        return workdir, meta

    def start_peers(self, prepared_dir: str) -> None:
        for i in range(self.n):
            if i == MEASURED:
                continue
            workdir, _meta = self._workdir(i, prepared_dir)
            ini = nodedrive.ini_text(
                prepared_quorum.net_ini(
                    self.peer_template, i, self.keys, self.peer_ports,
                    self.rpc_ports[i], self.ws_ports[i]),
                workdir=os.path.join(workdir, "db"), start_up="load")
            self.inis[i] = ini
            path = os.path.join(workdir, "validator.cfg")
            with open(path, "w") as fh:
                fh.write(ini)
            # no --start: it would force a fresh genesis over the
            # configuration's start_up=load
            self.peers[i] = self.children.spawn(
                [sys.executable, "-c", VALIDATOR, str(os.getpid()), path],
                os.path.join(workdir, "validator.log"))

    def start_measured(self, prepared_dir: str) -> dict:
        workdir, meta = self._workdir(MEASURED, prepared_dir)
        ini = nodedrive.ini_text(
            prepared_quorum.net_ini(self.ctx.ini_template, MEASURED,
                                    self.keys, self.peer_ports),
            workdir=os.path.join(workdir, "db"), start_up="load")
        self.inis[MEASURED] = ini
        self.node = nodedrive.boot(ini, serve=True)
        # the daemon's run loop (heartbeat, sweeps, operating mode), as
        # `python -m stellard_tpu` runs it behind setup().serve()
        threading.Thread(target=self.node.run, daemon=True,
                         name="node-run").start()
        self.rpc_ports[MEASURED] = self.node.http_server.port
        return meta

    def doors(self) -> list[int]:
        return list(self.rpc_ports)

    def validated_seqs(self) -> list[int]:
        out = []
        for port in self.doors():
            info = rpc(port, "server_info", timeout=10)["info"]
            out.append(int((info.get("validated_ledger") or {}).get("seq", 0)))
        return out

    def wait_meshed(self, timeout: float) -> None:
        """Every validator connected to the other three and every door
        reporting a validated ledger beyond the prepared one."""
        from stellard_tpu.testkit.tcpnet import wait_until

        first = self.node.ledger_master.closed_ledger().seq

        def ready():
            for i, p in self.peers.items():
                if p.poll() is not None:
                    raise SystemExit(
                        f"benchmark: validator {i} exited with "
                        f"{p.returncode} before the net meshed")
            for port in self.doors():
                info = rpc(port, "server_info", timeout=5)["info"]
                if info.get("peers") != self.n - 1:
                    return False
            return min(self.validated_seqs()) > first

        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                if ready():
                    return
            except OSError:
                pass
            time.sleep(0.5)
        raise SystemExit(f"benchmark: the net did not mesh and validate "
                         f"within {timeout:.0f}s")

    def door_summary(self, i: int) -> str:
        """What paces peer ``i``'s door: the open ledger's soft cap and
        the close cost behind it, and what the door held for how long
        (a program without the hold says so)."""
        counts = rpc(self.rpc_ports[i], "get_counts", timeout=20)
        txq = counts.get("txq") or {}
        metrics = txq.get("metrics") or {}
        door = counts.get("rpc_door") or {}
        return (f"peer {i} soft cap {metrics.get('txns_expected')} at "
                f"{metrics.get('per_tx_close_ms')} ms a transaction, "
                f"queued {txq.get('queued')}, "
                f"{(door.get('by_method') or {}).get('submit')} submits, "
                f"{door.get('submit_holds', 'no')} held for "
                f"{door.get('submit_hold_s', 0)} s")

    def diagnose(self) -> list[str]:
        """What the four validators say of themselves, for the line a
        run that stalled leaves behind: each door's state, round and
        peers, the measured validator's charges and queues, and the
        tail of each peer's log."""
        out = []
        for i, port in enumerate(self.doors()):
            try:
                info = rpc(port, "server_info", timeout=10)["info"]
                cons = rpc(port, "consensus_info", timeout=10)["info"]
                rnd = cons.get("round") or {}
                out.append(
                    f"validator {i}: {info.get('server_state')}, peers "
                    f"{info.get('peers')}, validated "
                    f"{(info.get('validated_ledger') or {}).get('seq')}, "
                    f"closed {cons.get('closed_seq')}, state "
                    f"{cons.get('validator_state')}, round "
                    f"{rnd.get('state')} seq {rnd.get('ledger_seq')} "
                    f"proposers {rnd.get('proposers')} disputes "
                    f"{rnd.get('disputes')}, load "
                    f"{info.get('load_factor')}")
                counts = rpc(port, "get_counts", timeout=20)
                out.append(f"validator {i}: " + json.dumps({
                    k: counts.get(k) for k in ("squelch", "relay", "byzantine",
                                               "acquisitions")
                    if k in counts} | {
                    "resource": (counts.get("resource") or {}).get("peers"),
                    "txq": {k: v for k, v in (counts.get("txq") or {}).items()
                            if isinstance(v, (int, float))}})[:1500])
            except Exception as exc:  # noqa: BLE001 - a diagnosis
                out.append(f"validator {i}: door {port}: {exc!r}")
        if self.node is not None:
            flat, programs = nodedrive.counters(
                self.node.verify_plane, self.node.hasher, self.node)
            out.append(f"validator {MEASURED} planes: {json.dumps(flat)}; "
                       f"programs {json.dumps(programs)[:1200]}")
            events = [ev for ev in
                      self.node.tracer.chrome_trace()["traceEvents"]
                      if ev["name"].startswith(("consensus.", "close."))]
            out.append(f"validator {MEASURED} last round events: " + "; ".join(
                f"{ev['name']}@{ev['ts'] / 1e6:.1f}s"
                f"+{ev.get('dur', 0) / 1e3:.0f}ms "
                f"{ {k: v for k, v in ev['args'].items() if k not in ('span', 'parent', 'trace', 'remote')} }"
                for ev in events[-40:])[:6000])
        for i in self.peers:
            log = os.path.join(self.ctx.work_root, f"validator-{i}",
                               "validator.log")
            try:
                with open(log) as fh:
                    tail = fh.read()[-1500:]
            except OSError:
                tail = ""
            if tail.strip():
                out.append(f"validator {i} log: ...{tail}")
        return out

    def start_generators(self, streams: dict[int, list]) -> None:
        """One ``loopgen.py`` a peer, its connections open, waiting."""
        conns = int(self.ctx.traffic["connections_per_peer"])
        for i, blobs in streams.items():
            base = os.path.join(self.ctx.work_root, f"gen-{i}")
            with open(base + ".txt", "w") as fh:
                fh.write("\n".join(b.hex() for b in blobs))
                fh.write("\n")
            self.gen_results[i] = base + ".json"
            self.gens[i] = self.children.spawn(
                [sys.executable, os.path.join(BENCH, "loopgen.py"),
                 base + ".txt", base + ".json", str(self.rpc_ports[i]),
                 str(conns), str(float(self.ctx.traffic.get("think_ms", 0)))],
                base + ".log", stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, text=True)
        for i, g in self.gens.items():
            line = g.stdout.readline().strip()
            if line != "ready":
                raise SystemExit(f"benchmark: generator {i} said {line!r}")

    def _tell(self, g: subprocess.Popen) -> None:
        try:
            g.stdin.write("\n")
            g.stdin.flush()
        except OSError:
            pass

    def go(self) -> None:
        for g in self.gens.values():
            self._tell(g)

    def stop_generators(self) -> dict[int, dict]:
        """Stop the loops -> what each generator sent, by peer."""
        for g in self.gens.values():
            self._tell(g)
        out = {}
        for i, g in self.gens.items():
            line = g.stdout.readline().strip()
            g.wait(timeout=30)
            if line != "done":
                raise SystemExit(f"benchmark: generator {i} ended with "
                                 f"{line!r} (rc={g.returncode})")
            with open(self.gen_results[i]) as fh:
                out[i] = json.load(fh)
        return out


def ledger_contents(port: int, ledger_hash: str) -> tuple[int, str, dict]:
    """-> (sequence, parent hash, {txid: engine result}) of a closed
    ledger, read over a door by its HASH (``ledger`` with its
    transactions expanded): a sequence can name a ledger this validator
    closed alone and left again, a hash cannot."""
    led = rpc(port, "ledger", {"ledger_hash": ledger_hash,
                               "transactions": True, "expand": True},
              timeout=120)["ledger"]
    txs = {}
    for j in led.get("transactions") or []:
        meta = j.get("metaData") or {}
        txs[j["hash"]] = meta.get("TransactionResult")
    return int(led["ledger_index"]), led["parent_hash"], txs


def validated_chain(port: int, tip: str, root: str) -> list[tuple]:
    """The validated chain behind the door at ``port``, from the ledger
    ``tip`` back to (not including) ``root``, oldest first:
    [(sequence, hash, {txid: result})]."""
    out = []
    cursor = tip.upper()
    while cursor != root.upper():
        seq, parent, txs = ledger_contents(port, cursor)
        out.append((seq, cursor, txs))
        cursor = parent.upper()
        if seq <= 1:
            raise KeyError(f"the chain behind {tip[:16]} does not reach "
                           f"{root[:16]}")
    return out[::-1]


def run(ctx) -> dict:
    tr, cfg = ctx.traffic, ctx.config
    pop = cfg["population"]
    count = int(float(tr["presign_tx_per_s"])
                * (ctx.seconds + float(tr["warmup_presign_s"])))
    problems: list[str] = []
    cap = ctx.capture()
    children = Children()
    net = Net(ctx, children)
    watch = None

    try:
        prepared_dir = prepared_quorum.ensure(cfg, ctx.ini_template,
                                              ctx.cache_dir)
        t_setup = time.perf_counter()
        net.start_peers(prepared_dir)
        meta = net.start_measured(prepared_dir)
        node = net.node
        marks = [("boot", time.perf_counter() - t_setup)]
        resumed = node.ledger_master.closed_ledger()
        if resumed.hash().hex() != meta["last_ledger"]["hash"]:
            raise SystemExit(
                f"benchmark: the validator resumed ledger {resumed.seq} "
                f"{resumed.hash().hex()[:16]}, the prepared store ends at "
                f"{meta['last_ledger']['seq']} "
                f"{meta['last_ledger']['hash'][:16]}")
        libs_ok, libs = nodedrive.host_libraries_ok()
        if not libs_ok:
            problems.append(f"host libraries: {libs}")
        entries = workload.payment_stream(
            seed=ctx.seed, pop=pop, params=tr, count=count)
        marks.append(("signed", time.perf_counter() - t_setup))
        nodedrive.wait_warm(node)
        marks.append(("warm", time.perf_counter() - t_setup))
        net.wait_meshed(float(tr["mesh_timeout_s"]))
        marks.append(("meshed", time.perf_counter() - t_setup))

        # sender s always to peer 1 + s mod 3: an account's sequence
        # stays in order at one door
        peers = sorted(net.peers)
        streams: dict[int, list] = {i: [] for i in peers}
        sent_to: dict[int, list] = {i: [] for i in peers}  # entry index
        for k, (blob, _planted, s, _d, _txid) in enumerate(entries):
            i = peers[s % len(peers)]
            streams[i].append(blob)
            sent_to[i].append(k)
        net.start_generators(streams)
        marks.append(("generators", time.perf_counter() - t_setup))
        ctx.say(f"store {meta['store_bytes']} bytes; {len(entries)} signed; "
                f"set-up, seconds from the peers' start: " + ", ".join(
                    f"{k} {v:.1f}" for k, v in marks))

        def drain_spans(_row=None) -> None:
            cap.collect_spans(node.tracer)

        watch = ValidatedWatch(node, on_new=drain_spans)
        net.go()

        # warm-up: validated rounds of the same traffic, unmeasured
        warm = int(tr["warmup_rounds"])
        carried = []

        def warm_done(row) -> bool:
            if row[2] > 0 and row not in carried:
                carried.append(row)
            return len(carried) >= warm

        warm_s = float(tr["warmup_timeout_s"])
        if watch.wait_for(warm_done, warm_s) is None:
            for line in net.diagnose():
                ctx.say(line)
            raise SystemExit(
                f"benchmark: {len(carried)} of {warm} warm-up rounds "
                f"validated in {warm_s:.0f} s; the validated ledgers seen: "
                f"{[(r[0], r[2]) for r in watch.seen]}")
        node.close_pipeline.flush(timeout=300)

        snap = functools.partial(nodedrive.counters, node.verify_plane,
                                 node.hasher, node)
        cap.start()
        watch.on_new = None
        t_mark = time.perf_counter()
        first = watch.wait_for(lambda r: r[3] > t_mark, 60.0)
        if first is None:
            for line in net.diagnose():
                ctx.say(line)
            raise SystemExit("benchmark: no ledger validated within 60 s "
                             "of the warm-up")
        cap.collect_spans(node.tracer)
        cap.spans.clear()
        watch.on_new = drain_spans
        before, mine_before = snap(), program_counters(node)

        # ---- the measured window ----
        with cap.annotate(WINDOW):
            t0 = first[3]
            last = watch.wait_for(
                lambda r: r[3] >= t0 + ctx.seconds, ctx.seconds + 120.0)
            if last is None:
                for line in net.diagnose():
                    ctx.say(line)
                raise SystemExit(
                    f"benchmark: no ledger validated in the 120 s behind "
                    f"the window's {ctx.seconds:.0f}")
            with cap.annotate("close_pipeline.flush"):
                node.close_pipeline.flush(timeout=300)
            t1 = time.perf_counter()
        # ---- end of the window ----
        after, mine_after = snap(), program_counters(node)
        cap.collect_spans(node.tracer)
        watch.on_new = None
        seqs_at_end = net.validated_seqs()
        ctx.say("the peers' doors at the window's end: " + "; ".join(
            net.door_summary(i) for i in sorted(net.peers)))
        window_s = t1 - t0
        window_rows = [r for r in watch.seen if first[3] < r[3] <= last[3]]

        sent = net.stop_generators()
        settle = int(tr["settle_rounds"])
        stop_at = len(watch.seen)
        watch.wait_for(lambda r: len(watch.seen) >= stop_at + settle, 60.0)
        show_host_price(ctx, node, entries)
        nodedrive.check_device_path(ctx, node, entries, cap, problems)
        cap.finish()  # writing the trace out: behind the window
        node.close_pipeline.flush(timeout=300)
        watch.stop()

        # ---- what the generators sent, and what the doors answered ----
        answers: dict[int, tuple] = {}  # entry index -> (sent, answered, outcome)
        outcomes: dict[str, int] = {}
        for i, got in sent.items():
            if got["exhausted"]:
                ctx.say(f"the signed stream of peer {i} ran out: raise "
                        f"presign_tx_per_s")
            for pos, t_sent, t_answered, outcome in got["results"]:
                answers[sent_to[i][pos]] = (t_sent, t_answered, outcome)
                outcomes[outcome] = outcomes.get(outcome, 0) + 1
        ctx.say("the doors answered: " + json.dumps(
            dict(sorted(outcomes.items()))))

        # ---- the validated ledgers' contents, over the measured door ----
        door = net.rpc_ports[MEASURED]
        end_seq, end_hash = watch.seen[-1][0], watch.seen[-1][1].hex()
        window_seqs = {r[0] for r in window_rows}
        by_hex = {e[4].hex().upper(): k for k, e in enumerate(entries)}
        model = workload.BalanceModel(int(pop["funded_drops"]),
                                      int(tr["fee_drops"]))
        landed: dict[int, int] = {}  # entry index -> validated seq
        hashes: dict[int, str] = {}
        counted: list[int] = []
        planted_in_ledger = 0
        try:
            chain = validated_chain(door, end_hash, resumed.hash().hex())
        except KeyError as exc:
            problems.append(f"the measured validator's door cannot serve "
                            f"its validated chain: {exc}")
            chain = []
        for seq, h, txs in chain:
            hashes[seq] = h
            for txid, result in txs.items():
                k = by_hex.get(txid)
                if k is None:
                    problems.append(f"ledger {seq} holds {txid[:16]}, "
                                    f"which no generator sent")
                    continue
                _blob, planted, s, d, _txid = entries[k]
                if planted:
                    planted_in_ledger += 1
                    continue
                landed[k] = seq
                if result in (0, "tesSUCCESS"):
                    model.applied(s, d, int(tr["amount_drops"]))
                    if seq in window_seqs:
                        counted.append(k)
                elif claimed_fee(result):
                    # a tec: nothing moved but the fee, and the sequence
                    model.delta[s] = model.delta.get(s, 0) - model.fee
                    model.seq[s] = model.sequence(s) + 1
        for seq, h, _n, _t in watch.seen:
            if hashes.get(seq, h.hex().upper()) != h.hex().upper():
                problems.append(f"the measured validator saw {h.hex()[:16]} "
                                f"validated at {seq}; its chain holds "
                                f"{hashes[seq][:16]} there")
        if planted_in_ledger:
            problems.append(f"{planted_in_ledger} planted signatures are in "
                            f"validated ledgers")
        validated = len(counted)

        attempted = planted_n = refused = acked_lost = fee_refused = 0
        for k, (t_sent, _t_answered, outcome) in answers.items():
            planted = entries[k][1]
            if planted:
                planted_n += 1
                if outcome == "temINVALID":
                    refused += 1
                else:
                    problems.append(f"planted signature "
                                    f"{entries[k][4].hex()[:16]} was "
                                    f"answered {outcome}")
            if outcome.startswith("telINSUF_FEE"):
                fee_refused += 1
            if not t0 <= t_sent <= t1:
                continue
            attempted += 1
            if (not planted and outcome in ("tesSUCCESS", "terQUEUED")
                    and k not in landed):
                acked_lost += 1
        window_planted = sum(1 for k, a in answers.items()
                             if entries[k][1] and t0 <= a[0] <= t1)
        failed = window_planted + acked_lost
        if refused != planted_n:
            problems.append(f"the doors refused {refused} of {planted_n} "
                            f"planted signatures")
        if fee_refused > 0.01 * max(len(answers), 1):
            ctx.say(f"{fee_refused} of {len(answers)} submits were refused "
                    f"by fee escalation: over 1%")

        # ---- agreement, quorum, read back ----
        peer_doors = [net.rpc_ports[i] for i in peers]
        check_agreement(
            {seq: [hashes[seq]] + [
                rpc(port, "ledger", {"ledger_index": seq})["ledger"]["hash"]
                for port in peer_doors]
             for seq in sorted(window_seqs) if seq in hashes}, problems)
        check_lag(seqs_at_end, problems)
        vn = node.overlay.node
        took_from = {landed[k] for k in counted}
        check_quorum(
            [(seq, h) for seq, h, _n, _t in window_rows if seq in took_from],
            vn.validations.validations_for, vn.key.public,
            int(cfg["net"]["quorum"]), problems)
        sample = nodedrive.seeded_sample(
            ctx.seed + 3, [entries[k][4] for k in counted],
            int(tr["tx_sample"]))
        for port in [door] + peer_doors[:2]:
            check_validated_transactions(port, sample, window_seqs, problems)
        n = int(tr["account_sample"])
        accounts = nodedrive.seeded_sample(ctx.seed + 1, model.touched(),
                                           n // 2)
        accounts += nodedrive.seeded_sample(
            ctx.seed + 2, list(range(int(pop["accounts"]))),
            n - len(accounts))
        check_accounts_at(door, end_hash, model, pop["name"], accounts,
                          problems)
        full = [r for r in window_rows if r[2] > 0]
        reclose = [h for _seq, h, _n, _t in nodedrive.seeded_sample(
            ctx.seed + 4, full, int(tr["reclose_ledgers"]))]
        window = nodedrive.delta(after, before)
        window.update({k: v - mine_before.get(k, 0)
                       for k, v in mine_after.items()})
    finally:
        if watch is not None:
            watch.stop()
        try:
            if net.node is not None:
                net.node.stop()
        finally:
            children.stop()
    # the net is stopped: the plain path over what it left on disk
    t_reclose = time.perf_counter()
    nodedrive.reclose_from_disk(net.inis[MEASURED], reclose, problems)
    nodedrive.reclose_from_disk(net.inis[peers[ctx.seed % len(peers)]],
                                reclose[:1], problems)
    ctx.say(f"re-closed {len(reclose)} ledger(s) from the measured "
            f"validator's disk and {len(reclose[:1])} from a peer's on the "
            f"plain path, {time.perf_counter() - t_reclose:.1f}s")

    rounds = len(window_rows)
    ctx.say(f"window {window_s:.2f}s, {rounds} validated rounds "
            f"({', '.join(str(r[2]) for r in window_rows)} tx), "
            f"{validated} validated of {attempted} sent inside it, "
            f"{acked_lost} acknowledged and lost, planted "
            f"{refused}/{planted_n}; validated seqs at the end "
            f"{seqs_at_end}")
    phases: dict[str, list] = {}
    for ev in cap.spans:
        if ev.get("ph") == "X" and ev["name"].startswith("consensus."):
            phases.setdefault(ev["name"], []).append(ev["dur"] / 1000.0)
            if ev["name"] == "consensus.round":
                a = ev.get("args") or {}
                phases.setdefault("txs/disputes/position_changes", []).append(
                    f"{a.get('txs')}/{a.get('disputes')}/"
                    f"{a.get('position_changes')}")
    if phases:
        ctx.say("round spans, ms each: " + "; ".join(
            f"{name} " + " ".join(
                v if isinstance(v, str) else f"{v:.0f}" for v in values)
            for name, values in sorted(phases.items())))
    ctx.say("program: " + ", ".join(
        f"{k} {window[k]}" for k in sorted(window)
        if k.startswith(("replay.", "relay.", "netverify.", "overlay.",
                         "seal."))))
    window.update({
        "window_s": window_s, "attempted": attempted, "txs": validated,
        # the measured validator's own closes inside the window (the
        # seal counts them), else the rounds it saw validated
        "closes": window.get("seal.closes") or rounds,
        "quorum.peer_lag_ledgers": max(seqs_at_end) - min(seqs_at_end),
    })
    return {
        "correct": not problems,
        "problems": problems,
        "attempted": attempted,
        "failed": failed,
        "t_first_measured": t0,
        # every ledger the measured validator saw validated, warm-up
        # and settling included (for whoever re-closes them all)
        "validated_ledgers": [(r[0], r[1].hex()) for r in watch.seen],
        "stores": dict(net.inis),
        "annotations": ["close_pipeline.flush", "check_device_path"],
        "end_to_end": {
            "validated_tx_per_s": stats.rate(validated, window_s),
        },
        "sources": {
            "counters": window,
            "spans": cap.spans,
            "capture": cap,
        },
    }


def show_host_price(ctx, node, entries: list) -> None:
    """Before the device-path check, where the router has never priced
    its host arm: one batch of ``min_device_batch`` of the run's own
    signatures. The router explores an unmeasured arm with the first
    batch large enough to be routed, and measures the host only from
    batches of 8 or more: a standalone node's first close teaches it,
    but a validator fed by relay sees its signatures one or two at a
    time and would send the check's 16,384 to the host "to explore"
    (PERF.md section 6, PR 32). The check itself stays as every node
    cell has it."""
    from stellard_tpu.crypto.backend import VerifyRequest
    from stellard_tpu.protocol.sttx import SerializedTransaction

    plane = node.verify_plane
    if (plane.get_json().get("model") or {}).get("cpu_persig_ms") is not None:
        return
    requests = []
    for blob, *_rest in entries[-plane.min_device_batch:]:
        tx = SerializedTransaction.from_bytes(blob)
        requests.append(VerifyRequest(
            tx.signing_pub_key, tx.signing_hash(), tx.signature))
    plane.verify_many(requests)
    ctx.say(f"the router had no price for its host arm: shown one batch of "
            f"{len(requests)} (host "
            f"{plane.get_json()['model']['cpu_persig_ms']} ms a signature)")


def claimed_fee(result) -> bool:
    """A ``tec`` result, as a door prints it (a token, or its code)."""
    if isinstance(result, str):
        return result.startswith("tec")
    return isinstance(result, int) and 100 <= result < 200


def check_agreement(hashes: dict[int, list], problems: list) -> None:
    """ONE hash a validated sequence, over the four doors."""
    for seq, seen in sorted(hashes.items()):
        if len(set(seen)) != 1:
            problems.append(
                f"validated sequence {seq} has {len(set(seen))} hashes over "
                f"the {len(seen)} doors: {sorted({h[:16] for h in seen})}")


def check_lag(seqs_at_end: list[int], problems: list) -> None:
    """At the window's end the measured validator is no more than one
    round behind the furthest peer."""
    if max(seqs_at_end) - seqs_at_end[MEASURED] > 1:
        problems.append(
            f"at the window's end the measured validator had validated "
            f"{seqs_at_end[MEASURED]}, the furthest peer {max(seqs_at_end)}")


def check_quorum(ledgers: list, validations_for, own_public: bytes,
                 quorum: int, problems: list) -> None:
    """Every ledger the count took transactions from has ``quorum``
    trusted validations in the measured validator's store, its own
    among them."""
    for seq, h in ledgers:
        trusted = [v for v in validations_for(h) if v.trusted]
        own = any(v.signer == own_public for v in trusted)
        if len(trusted) < quorum or not own:
            problems.append(
                f"ledger {seq} counted with {len(trusted)} trusted "
                f"validations in the measured validator's store, its own "
                f"{'among' if own else 'NOT among'} them")


def check_validated_transactions(port: int, txids: list, ledger_seqs: set,
                                 problems: list) -> None:
    """Each sampled counted transaction is found by ``tx`` behind this
    door in one of the window's ledgers, ``tesSUCCESS`` and
    ``validated: true``."""
    for txid in txids:
        h = txid.hex().upper()
        res = rpc(port, "tx", {"transaction": h})
        meta = res.get("meta") or {}
        ok = (res.get("hash") == h
              and res.get("ledger_index") in ledger_seqs
              and res.get("validated") is True
              and meta.get("TransactionResult") in (0, "tesSUCCESS"))
        if not ok:
            problems.append(
                f"tx {h[:16]} behind door {port}: ledger_index="
                f"{res.get('ledger_index')} validated="
                f"{res.get('validated')} result="
                f"{meta.get('TransactionResult')} error={res.get('error')}")


def check_accounts_at(port: int, ledger_hash: str, model, population: str,
                      sample: list[int], problems: list) -> None:
    """``account_info`` of each sampled account AT the validated ledger
    ``ledger_hash`` against the benchmark's own arithmetic over the
    validated chain up to it (the open ledger holds what no quorum has
    seen)."""
    from stellard_tpu.protocol.keys import encode_account_id

    keys = workload.population_keys(population, sample)
    for i in sample:
        acct = encode_account_id(keys[i].account_id)
        res = rpc(port, "account_info", {"account": acct,
                                         "ledger_hash": ledger_hash})
        data = res.get("account_data") or {}
        got = (data.get("Balance"), data.get("Sequence"))
        want = (str(model.balance(i)), model.sequence(i))
        if got != want:
            problems.append(f"account_info[{i}] at ledger "
                            f"{ledger_hash[:16]} answered {got}, the model "
                            f"says {want}")
