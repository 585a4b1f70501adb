"""On-chip measurement sweep: verify kernel (batch x unroll) + tree hashing.

Run on a host with a TPU (`python tools/kernel_sweep.py`). The parent
stays off JAX and each configuration runs in its own SUBPROCESS, one
after another: the kernel knobs are read once at module import, a chip
belongs to one process at a time, and a configuration that wedges or
that the compiler refuses can never kill the whole sweep. The signed
test set is cached on disk so retries are cheap.
"""
import os, sys, time, subprocess

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
import numpy as np
sys.path.insert(0, REPO)

CACHE = "/tmp/sigset.npz"

SIGSET_N = 16384  # must cover 2x the largest swept batch for input cycling

# measured (unroll, comb, batch, rate) rows; the winner is persisted to
# KERNEL_TUNING.json so an unattended bench.py run (the driver's
# end-of-round invocation) picks the tuned kernel without a human in
# the loop
RESULTS: list[dict] = []
TUNING_PATH = os.path.join(REPO, "KERNEL_TUNING.json")


def ensure_sigset():
    if os.path.exists(CACHE):
        if len(np.load(CACHE)["pubs"]) >= SIGSET_N:
            return
        os.remove(CACHE)  # stale smaller cache: would re-enable memoization
    from stellard_tpu.protocol.keys import KeyPair
    rng = np.random.default_rng(0)
    keys = [KeyPair.from_seed(bytes(rng.integers(0,256,32,dtype=np.uint8))) for _ in range(64)]
    N = SIGSET_N
    msgs = [bytes(rng.integers(0,256,32,dtype=np.uint8)) for _ in range(N)]
    sigs = [keys[i%64].sign(msgs[i]) for i in range(N)]
    pubs = [keys[i%64].public for i in range(N)]
    np.savez(CACHE,
             pubs=np.frombuffer(b"".join(pubs), np.uint8).reshape(N,32),
             msgs=np.frombuffer(b"".join(msgs), np.uint8).reshape(N,32),
             sigs=np.frombuffer(b"".join(sigs), np.uint8).reshape(N,64))

def one_config(unroll, batches, comb="mxu", hoist=0, group=0, impl="xla",
               block=512, check="bytes", wire="raw"):
    """Run one (unroll, comb-select, hoist, group, impl, check, batches)
    measurement in a SUBPROCESS so each one starts from a fresh runtime
    and a wedge can't kill the sweep. Inputs are cycled across distinct sets
    so no layer can memoize identical submissions. impl="pallas" runs
    the whole-verify-in-VMEM kernel (ops/ed25519_pallas.py) with grid
    block size `block`; check="point" runs the inversion-free projective
    final check (stacked double-width decompress)."""
    code = f'''
import os, sys, time
import numpy as np
os.environ.pop("JAX_PLATFORMS", None)
os.environ["STELLARD_VERIFY_UNROLL"] = "{unroll}"
os.environ["STELLARD_COMB_SELECT"] = "{comb}"
os.environ["STELLARD_HOIST_SELECT"] = "{hoist}"
os.environ["STELLARD_GROUP_OPS"] = "{group}"
os.environ["STELLARD_PALLAS_BLOCK"] = "{block}"
os.environ["STELLARD_VERIFY_CHECK"] = "{check}"
os.environ["STELLARD_WIRE"] = "{wire}"
sys.path.insert(0, {REPO!r})
import jax
if os.environ.get("STELLARD_SWEEP_ALLOW_CPU") != "1":
    assert jax.devices()[0].platform != "cpu", "no tpu"
from stellard_tpu.utils.xlacache import enable_compilation_cache
enable_compilation_cache()
from stellard_tpu.ops.ed25519_jax import prepare_batch
if "{impl}" == "pallas":
    from stellard_tpu.ops.ed25519_pallas import (
        verify_kernel_pallas as verify_kernel)
else:
    from stellard_tpu.ops.ed25519_jax import verify_kernel
z = np.load("{CACHE}")
N = len(z["pubs"])
for batch in {batches}:
    sets = []
    if batch <= N:
        orderings = [np.arange(s0, s0 + batch)
                     for s0 in range(0, min(4 * batch, N), batch)
                     if s0 + batch <= N]
    else:
        # batch exceeds the cached sigset: tile it and use distinct
        # permutations so no layer ever sees two identical submissions
        reps = -(-batch // N)
        base = np.tile(np.arange(N), reps)[:batch]
        rng = np.random.default_rng(0)
        orderings = [base, rng.permutation(base)]
    for idx in orderings:
        sets.append(prepare_batch(
            [z["pubs"][i].tobytes() for i in idx],
            [z["msgs"][i].tobytes() for i in idx],
            [z["sigs"][i].tobytes() for i in idx],
        ))
    t0=time.time(); out = verify_kernel(**sets[0]); out.block_until_ready()
    print(f"unroll={unroll} comb={comb} hoist={hoist} group={group} impl={impl} block={block} check={check} wire={wire} batch={{batch}} compile {{time.time()-t0:.0f}}s", flush=True)
    assert np.asarray(out).all()
    t0=time.time(); n=0
    while time.time()-t0 < 5:
        verify_kernel(**sets[n % len(sets)]).block_until_ready(); n+=1
    dt=(time.time()-t0)/n
    print(f"RESULT unroll={unroll} comb={comb} hoist={hoist} group={group} impl={impl} block={block} check={check} wire={wire} batch={{batch}} lat={{dt*1000:.1f}}ms rate={{batch/dt:,.0f}} sigs/s", flush=True)
'''
    try:
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=1500)
    except subprocess.TimeoutExpired:
        print(f"unroll={unroll} comb={comb} hoist={hoist} group={group} "
              f"impl={impl} block={block} check={check} wire={wire} batches={batches}: TIMED OUT "
              f"(wedged device?) — skipping", flush=True)
        return False
    out = "\n".join(l for l in (r.stdout + r.stderr).splitlines()
                    if "WARNING" not in l and l.strip())
    print(out, flush=True)
    for line in out.splitlines():
        # RESULT unroll=U comb=C batch=B lat=L rate=R sigs/s
        if line.startswith("RESULT unroll="):
            try:
                kv = dict(p.split("=", 1) for p in line.split()[1:-1]
                          if "=" in p)
                RESULTS.append({
                    "unroll": int(kv["unroll"]),
                    "comb": kv["comb"],
                    "hoist": int(kv.get("hoist", 0)),
                    "group": int(kv.get("group", 0)),
                    "impl": kv.get("impl", "xla"),
                    "block": int(kv.get("block", 512)),
                    "check": kv.get("check", "bytes"),
                    "wire": kv.get("wire", "digits"),
                    "batch": int(kv["batch"]),
                    "rate": float(kv["rate"].replace(",", "")),
                })
            except (KeyError, ValueError):
                pass
    return r.returncode == 0

def tree_hash_bench():
    code = f'''
import os, sys, time
import numpy as np
os.environ.pop("JAX_PLATFORMS", None)
sys.path.insert(0, {REPO!r})
import jax
if os.environ.get("STELLARD_SWEEP_ALLOW_CPU") != "1":
    assert jax.devices()[0].platform != "cpu", "no tpu"
from stellard_tpu.utils.xlacache import enable_compilation_cache
enable_compilation_cache()
from stellard_tpu.crypto.backend import make_hasher
from stellard_tpu.state.shamap import SHAMap, SHAMapItem, TNType

def build(n, seed):
    rng = np.random.default_rng(seed)
    m = SHAMap(TNType.ACCOUNT_STATE)
    for i in range(n):
        m.set_item(SHAMapItem(rng.bytes(32), rng.bytes(int(rng.integers(40, 600)))))
    return m

for n_leaves in (1000, 5000):
    for name in ("cpu", "tpu"):
        h = make_hasher(name)
        m = build(n_leaves, n_leaves)
        m.hash_batch = h
        t0=time.time(); m.get_hash(); c=time.time()-t0
        m2 = build(n_leaves, n_leaves + 1)
        m2.hash_batch = h
        t0=time.time(); m2.get_hash(); dt=time.time()-t0
        print(f"RESULT treehash backend={{name}} leaves={{n_leaves}} first={{c:.2f}}s warm={{dt*1000:.0f}}ms", flush=True)
'''
    try:
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=1500)
    except subprocess.TimeoutExpired:
        print("treehash bench TIMED OUT — skipping", flush=True)
        return
    print("\n".join(l for l in (r.stdout+r.stderr).splitlines()
                    if "WARNING" not in l and l.strip()), flush=True)
    if r.returncode != 0 or "RESULT treehash" not in r.stdout:
        # a silent miss here cost two windows of the one unmeasured
        # number the replay leg hinges on — make the failure loud
        print("treehash bench FAILED (no RESULT rows)", flush=True)

def transfer_probe():
    """Host->device transfer rate for one prepared verify batch — the
    e2e headline's gap to the device-only rate points at the transfer
    path (ROADMAP S2); this measures it directly, for the narrow (int8
    digit) wire format."""
    code = f'''
import os, sys, time
import numpy as np
os.environ.pop("JAX_PLATFORMS", None)
sys.path.insert(0, {REPO!r})
import jax
if os.environ.get("STELLARD_SWEEP_ALLOW_CPU") != "1":
    assert jax.devices()[0].platform != "cpu", "no tpu"
from stellard_tpu.ops.ed25519_jax import prepare_batch
import jax.numpy as jnp
z = np.load("{CACHE}")
B = 16384
idx = list(range(B))
for wire in ("raw", "digits"):
    os.environ["STELLARD_WIRE"] = wire
    inputs = prepare_batch(
        [z["pubs"][i % len(z["pubs"])].tobytes() for i in idx],
        [z["msgs"][i % len(z["msgs"])].tobytes() for i in idx],
        [z["sigs"][i % len(z["sigs"])].tobytes() for i in idx],
        device_put=False,
    )
    nbytes = sum(np.asarray(v).nbytes for v in inputs.values())
    # one warm put, then timed puts of fresh host copies
    for _ in range(2):
        res = {{k: jnp.asarray(v) for k, v in inputs.items()}}
        jax.block_until_ready(list(res.values()))
    t0 = time.time(); n = 0
    while time.time() - t0 < 5:
        res = {{k: jnp.asarray(np.ascontiguousarray(v)) for k, v in inputs.items()}}
        jax.block_until_ready(list(res.values()))
        n += 1
    dt = (time.time() - t0) / n
    print(f"RESULT transfer wire={{wire}} batch={{B}} bytes={{nbytes}} per_put={{dt*1000:.1f}}ms rate={{nbytes/dt/1e6:.1f}} MB/s", flush=True)
'''
    try:
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=900)
    except subprocess.TimeoutExpired:
        print("transfer probe TIMED OUT — skipping", flush=True)
        return
    print("\n".join(l for l in (r.stdout + r.stderr).splitlines()
                    if "WARNING" not in l and l.strip()), flush=True)
    if r.returncode != 0 or "RESULT transfer" not in r.stdout:
        print("transfer probe FAILED (no RESULT row)", flush=True)


def write_tuning():
    if not RESULTS:
        return
    import json

    # merge with the existing tuning history: a partial sweep (a wedge,
    # a timeout) must never bury a better configuration measured earlier —
    # the winner is the best across ALL recorded rows, deduped by config
    rows = list(RESULTS)
    try:
        with open(TUNING_PATH) as f:
            prior = json.load(f).get("all", [])
    except (OSError, ValueError):
        prior = []
    def key(r):
        return (r.get("unroll", 1), r.get("comb", "mxu"),
                r.get("hoist", 0), r.get("group", 0),
                r.get("impl", "xla"), r.get("block", 512),
                r.get("check", "bytes"), r.get("wire", "digits"),
                r.get("batch"))
    seen = {key(r) for r in rows}
    for r in prior:
        # normalize historical source-revision labels: "rowpad" IS the
        # current xla kernel (hoist=0/group=0); "legacy" rows measured
        # superseded source and are dropped
        impl = r.get("impl", "xla")
        if impl == "legacy":
            continue
        if impl == "rowpad":
            r = {**r, "impl": "xla", "hoist": 0, "group": 0}
        if key(r) not in seen:      # keep older rows not re-measured
            rows.append(r)
            seen.add(key(r))
    # the persisted WINNER must keep the reference verify semantics:
    # check=point rows are recorded in "all" for the A/B evidence, but
    # auto-applied tuning never flips the consensus-critical check mode
    # (see crypto.backend.apply_kernel_tuning) — so the winner is the
    # best bytes-mode row
    bytes_rows = [r for r in rows if r.get("check", "bytes") == "bytes"]
    best = max(bytes_rows or rows, key=lambda r: r["rate"])
    RESULTS[:] = rows
    # temp + rename: an interrupted dump must never leave a truncated
    # file for the driver's unattended bench.py to trip over. The file
    # is committed with the round like the other bench artifacts — it
    # documents the measured-best kernel config.
    tmp = TUNING_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump({
            "unroll": best["unroll"],
            "comb": best["comb"],
            "hoist": best.get("hoist", 0),
            "group": best.get("group", 0),
            "impl": best.get("impl", "xla"),
            "block": best.get("block", 512),
            "check": best.get("check", "bytes"),
            # a row measured before the wire field existed carries NO
            # wire opinion — writing "digits" here would drag the bench
            # back to the fat wire via apply_kernel_tuning
            **({"wire": best["wire"]} if "wire" in best else {}),
            "batch": best["batch"],
            "rate": best["rate"],
            "all": RESULTS,
            "note": "measured by tools/kernel_sweep.py on the current "
                    "kernel source (rowpad fe_mul; hoist/group gates; "
                    "impl=xla|pallas)",
        }, f, indent=1)
    os.replace(tmp, TUNING_PATH)
    print(f"TUNING -> {TUNING_PATH}: unroll={best['unroll']} "
          f"comb={best['comb']} batch={best['batch']} "
          f"rate={best['rate']:,.0f}", flush=True)


if __name__ == "__main__":
    # Config list as of the rowpad + hoisted-select kernel (measured
    # r4: rowpad in-loop-select hit 46.3k/71.6k/99.9k/103.4k sigs/s at
    # 4096/8192/16384/32768; unroll>1 measured flat, so the sweep
    # focuses on batch scaling + comb A/B for the hoisted form).
    ensure_sigset()
    # Measured 2026-07-31 (builders' sweep, pre-round): hoist=0/group=0 @16384 =
    # 100.7k sigs/s (reproduces the a7910e1 winner); group=1 = 63.2k
    # (grouping is the regression); hoisted+grouped = 63.7k. Standing
    # record: 103.4k @32768 (prior window). Remaining questions,
    # ordered so a short window answers the biggest first:
    # 1) the raw-bytes wire on the known winner config (the e2e
    #    headline's transfer leg: 129 B/sig vs 193; kernel math
    #    unchanged, so rate should match the 103.4k record while e2e
    #    improves), then the digits wire as the A/B control:
    one_config(1, [16384, 32768], wire="raw")
    write_tuning()
    # 2) the inversion-free projective final check (~15% fewer
    #    sequential wide ops than the ref10 byte-compare shape):
    one_config(1, [16384, 32768], check="point")
    # 2) the Pallas whole-verify-in-VMEM kernel vs the XLA formulation
    #    (same block set for both check modes — the comparison must not
    #    confound formulation with block size):
    one_config(1, [16384], impl="pallas", block=512)
    write_tuning()  # interim: a wedge below must not lose what's measured
    # 2b) host->device transfer rate (is the e2e headline
    #     transfer-bound?)
    transfer_probe()
    # 3) tree-hash first/warm timings — NEVER yet measured on-chip
    #    (dropped by wedges in both r4 windows) and the replay leg's
    #    device share hinges on them; ahead of the remaining verify A/Bs
    tree_hash_bench()
    one_config(1, [16384], impl="pallas", block=1024)
    write_tuning()  # interim after every late config: the 5400s outer
    one_config(1, [16384], impl="pallas", block=512, check="point")
    write_tuning()  # deadline must never lose a completed measurement
    # 4) batch scaling of the XLA winner beyond the 32768 record:
    one_config(1, [32768, 65536], group=0)
    write_tuning()
    # 5) consensus-close-sized batches (VERDICT r4 #8): can ANY device
    #    config beat threaded-native at ~300-2048 sigs? Pallas small
    #    blocks are the candidate; the XLA row is the control. If both
    #    lose to the host at these sizes, the router's CPU floor on the
    #    close leg is the measured-optimal answer and PERF.md says so.
    one_config(1, [512, 2048], impl="pallas", block=256)
    write_tuning()
    one_config(1, [512, 2048])
    write_tuning()
    # 6) in-loop comb-select strategies at the winning defaults:
    one_config(1, [16384], comb="mxu_split")
    write_tuning()
    one_config(1, [16384], comb="vpu")
    write_tuning()
    print("SWEEP DONE", flush=True)
