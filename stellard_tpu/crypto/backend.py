"""The crypto-plane backend seam: pluggable batched verifier/hasher.

This is the factory-registry pattern the reference uses for NodeStore
backends (/root/reference/src/ripple_core/nodestore/api/Factory.h:27-44,
Manager::make_Database), applied to the crypto hot path per the north
star: `signature_backend = cpu|tpu` in the node config selects which
implementation coalesced JobQueue-style verification batches run on.

- ``cpu``: per-signature verification via the host library (the libsodium
  role), threaded over the batch.
- ``tpu``: the batched JAX kernel (ops.ed25519_jax) — one device program
  over the whole batch.
"""

from __future__ import annotations

import logging
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Callable, Iterable, Optional, Sequence

import numpy as np

log = logging.getLogger("stellard.device")

# Serializes the FIRST jax import across threads. At node setup the
# verify prewarm thread and the genesis ledger hash (a forced-device
# hash plane) can both trigger jax's first import concurrently, and
# jax's internal circular imports make a concurrent first import crash
# with "partially initialized module jax.numpy has no attribute ..." —
# one thread must complete the whole import chain before any other
# device path touches it.
_JAX_IMPORT_LOCK = threading.Lock()
_cache_enabled = False  # guarded by _JAX_IMPORT_LOCK


def ensure_jax():
    """Import (and fully initialize) jax under a process-wide lock;
    returns the module. Every device-backend entry point calls this
    instead of a bare `import jax` so two threads can never interleave
    jax's first partial initialization — and so the persistent compile
    cache (utils/xlacache.py) is on before the first program compiles,
    whichever entry point (node, --replay, a tool) got here first."""
    global _cache_enabled
    with _JAX_IMPORT_LOCK:
        import jax
        import jax.numpy  # noqa: F401 — force the circular tail too

        if not _cache_enabled:
            from ..utils.xlacache import enable_compilation_cache

            enable_compilation_cache()
            _cache_enabled = True
        return jax


def parse_mesh(value) -> str:
    """Canonicalize a ``mesh=`` config value (the multi-chip width axis):
    returns ``"auto"`` or the string form of a non-negative int. ``0``
    means "no mesh requested" — which executes as a width-1 mesh, the
    SAME routed code path as every other width (there is no separate
    single-device fork). Anything else raises: a width toggle must not
    silently fail open into an unintended topology."""
    if value is None:
        return "0"
    s = str(value).strip().lower()
    if s in ("", "off"):
        return "0"
    if s == "auto":
        return "auto"
    try:
        n = int(s)
    except ValueError:
        raise ValueError(
            f"mesh= must be a non-negative integer or 'auto', got {value!r}"
        ) from None
    if n < 0:
        raise ValueError(
            f"mesh= must be a non-negative integer or 'auto', got {value!r}"
        )
    return str(n)


def mesh_wants_width(value) -> bool:
    """True when a ``mesh=`` value asks for MORE than one chip (so the
    three-way host/1-chip/N-chip routing should grow a separate 1-chip
    arm). "auto" counts: its effective width is only known at device
    discovery."""
    m = parse_mesh(value)
    return m == "auto" or int(m) > 1


def resolve_mesh_width(mesh, n_visible: int, pow2: bool = False) -> int:
    """Effective mesh width for a backend: ``auto`` -> every visible
    device, N -> min(N, visible) (clamped with a warning — a config
    asking for more chips than exist must degrade loudly, not die),
    0 -> 1. ``pow2=True`` additionally rounds DOWN to a power of two
    (the hash plane's leaf batcher pads row counts to powers of two, so
    only pow2 widths divide its batches evenly)."""
    m = parse_mesh(mesh)
    n_visible = max(1, n_visible)
    want = n_visible if m == "auto" else max(1, int(m))
    if want > n_visible:
        log.warning(
            "mesh=%s requests %d devices but only %d are visible — "
            "clamping to %d", m, want, n_visible, n_visible,
        )
    width = max(1, min(want, n_visible))
    if pow2:
        width = 1 << (width.bit_length() - 1)
    return width


@dataclass(frozen=True)
class VerifyRequest:
    public: bytes  # 32-byte Ed25519 public key
    signing_hash: bytes  # 32-byte message (prefixed SHA-512-half)
    signature: bytes  # 64-byte detached signature


class BatchVerifier:
    """Interface: verify a batch of Ed25519 signatures."""

    name = "abstract"

    def verify_batch(self, batch: Sequence[VerifyRequest]) -> np.ndarray:
        raise NotImplementedError


class BatchHasher:
    """Interface: batched SHA-512-half with 4-byte domain prefixes.

    Hashers are callable (the SHAMap hash_batch seam); implementations
    may additionally expose ``hash_tree(root)`` for whole-tree device
    pipelines (state.shamap.compute_hashes detects it)."""

    name = "abstract"

    # routing counters (bench legs report a "device share" so a hasher
    # that silently falls back to host cannot look device-accelerated)
    device_nodes = 0
    host_nodes = 0

    def prefix_hash_batch(self, prefixes: Sequence[int], payloads: Sequence[bytes]) -> list[bytes]:
        raise NotImplementedError

    def hash_packed(self, buf: bytes, offsets: Sequence[int]) -> list[bytes]:
        """Hash PACKED messages (state.shamap.encode_nodes layout: every
        message carries its 4-byte domain prefix, `offsets` is the n+1
        boundary list). Default adapter slices back into the
        (prefixes, payloads) shape; real backends override with a
        zero-slicing path."""
        prefixes, payloads = [], []
        for i in range(len(offsets) - 1):
            msg = buf[offsets[i] : offsets[i + 1]]
            prefixes.append(int.from_bytes(msg[:4], "big"))
            payloads.append(msg[4:])
        return self.prefix_hash_batch(prefixes, payloads)

    def __call__(self, prefixes, payloads):
        return self.prefix_hash_batch(prefixes, payloads)


# name -> (factory, accepted-option names or None=accept anything).
# Declared options make the factories fail LOUDLY on unknown keys: the
# config plumbing (Config -> Node -> VerifyPlane/make_watched_hasher ->
# here) hands operator-written kwargs through, and a typo'd or
# unsupported option must raise at node build, never silently no-op.
_VERIFIERS: dict[str, tuple[Callable[..., BatchVerifier],
                            Optional[frozenset]]] = {}
_HASHERS: dict[str, tuple[Callable[..., BatchHasher],
                          Optional[frozenset]]] = {}


def _check_options(kind: str, name: str, accepted: Optional[frozenset],
                   kwargs: dict) -> None:
    if accepted is None:
        return  # undeclared factory (test doubles): accept anything
    unknown = sorted(set(kwargs) - accepted)
    if unknown:
        raise ValueError(
            f"{kind} backend {name!r} does not accept option(s) "
            f"{unknown}; accepted: {sorted(accepted) or '(none)'}"
        )


def register_verifier(name: str, factory: Callable[..., BatchVerifier],
                      options: Optional[Iterable[str]] = None) -> None:
    _VERIFIERS[name] = (
        factory, frozenset(options) if options is not None else None
    )


def register_hasher(name: str, factory: Callable[..., BatchHasher],
                    options: Optional[Iterable[str]] = None) -> None:
    _HASHERS[name] = (
        factory, frozenset(options) if options is not None else None
    )


def make_verifier(name: str, **kwargs) -> BatchVerifier:
    if name not in _VERIFIERS:
        raise KeyError(f"unknown signature backend {name!r}; have {sorted(_VERIFIERS)}")
    factory, accepted = _VERIFIERS[name]
    _check_options("signature", name, accepted, kwargs)
    return factory(**kwargs)


def make_hasher(name: str, **kwargs) -> BatchHasher:
    if name not in _HASHERS:
        raise KeyError(f"unknown hash backend {name!r}; have {sorted(_HASHERS)}")
    factory, accepted = _HASHERS[name]
    _check_options("hash", name, accepted, kwargs)
    return factory(**kwargs)


# --------------------------------------------------------------------------
# cpu backend


class CpuVerifier(BatchVerifier):
    """Host-library per-signature verification (the libsodium role of the
    reference: StellarPublicKey::verifySignature), threaded over the batch."""

    name = "cpu"
    impl = "openssl"

    _shared_pool: ThreadPoolExecutor | None = None

    def __init__(self, threads: int = 4):
        if threads > 1:
            if CpuVerifier._shared_pool is None:
                # the pool lives as long as the process: its threads
                # enter the intake's role and never leave
                from ..node.tracer import THREAD_ROLES

                CpuVerifier._shared_pool = ThreadPoolExecutor(
                    max_workers=threads, thread_name_prefix="cpu-verify",
                    initializer=THREAD_ROLES.enter, initargs=("intake",),
                )
            self._pool = CpuVerifier._shared_pool
        else:
            self._pool = None

    def verify_batch(self, batch: Sequence[VerifyRequest]) -> np.ndarray:
        from ..protocol.keys import verify_signature

        def one(req: VerifyRequest) -> bool:
            return verify_signature(req.public, req.signing_hash, req.signature)

        if self._pool is None or len(batch) < 64:
            return np.array([one(r) for r in batch], bool)
        return np.array(list(self._pool.map(one, batch)), bool)


class NativeVerifier(BatchVerifier):
    """Batched C++ verification (native/src/ed25519_verify.cc): the whole
    batch crosses into native code in ONE call, so per-signature cost is
    pure curve arithmetic — no per-call interpreter work and no GIL.
    This is the closest analog of the reference's libsodium hot path
    (StellarPublicKey::verifySignature) and the default host side of the
    verify plane when the toolchain is present."""

    name = "cpu"  # fills the host role; .impl says which implementation
    impl = "native"

    def __init__(self, **_):
        from ..native import Ed25519NativeVerify

        self._impl = Ed25519NativeVerify()

    def verify_batch(self, batch: Sequence[VerifyRequest]) -> np.ndarray:
        return self._impl.verify_batch(
            [r.public for r in batch],
            [r.signing_hash for r in batch],
            [r.signature for r in batch],
        )


def _host_verifier_factory(**kwargs) -> BatchVerifier:
    """The ``cpu`` backend resolves to the fastest available host
    implementation: native C++ batch verify, else the per-signature
    host-library path. ``STELLARD_HOST_VERIFY`` overrides: ``python`` /
    ``openssl`` force the host-library path, ``native`` requires the
    C++ kernel (raises if unbuildable), ``auto`` (default) prefers
    native with graceful degradation. Unknown values are rejected — a
    perf/debug toggle must not silently no-op."""
    import os

    choice = os.environ.get("STELLARD_HOST_VERIFY", "auto").lower()
    if choice in ("python", "openssl"):
        return CpuVerifier(**kwargs)
    if choice == "native":
        return NativeVerifier()
    if choice not in ("auto", ""):
        raise ValueError(
            f"STELLARD_HOST_VERIFY={choice!r}: expected auto|native|"
            "python|openssl"
        )
    try:
        return NativeVerifier()
    except Exception:  # noqa: BLE001 — toolchain-less box: degrade
        return CpuVerifier(**kwargs)


class CpuHasher(BatchHasher):
    name = "cpu"

    def prefix_hash_batch(self, prefixes, payloads):
        from ..utils.hashes import prefix_hash

        self.host_nodes += len(prefixes)
        return [prefix_hash(p, d) for p, d in zip(prefixes, payloads)]

    def hash_packed(self, buf, offsets):
        # a packed message == prefix ‖ payload, and
        # prefix_hash(p, d) == sha512_half(p4 ‖ d): hash slices directly
        from ..utils.hashes import sha512_half

        mv = memoryview(buf)
        n = len(offsets) - 1
        self.host_nodes += n
        return [
            sha512_half(mv[offsets[i] : offsets[i + 1]]) for i in range(n)
        ]


# --------------------------------------------------------------------------
# tpu backend


class TransferMeter:
    """Host<->device transfer honesty counter (ISSUE 16): every device
    plane counts its host->device shipments and device->host readbacks
    so residency can't silently regress — a "fused" close that quietly
    round-trips per level shows up as a readback count proportional to
    tree depth instead of the pinned one-per-tree. ``uploads`` counts
    logical shipment SETS (one per dispatched program, however many
    arrays ride it); ``readbacks`` counts host-blocking device->host
    transfers — the residency signal."""

    __slots__ = ("uploads", "readbacks", "bytes_up", "bytes_down")

    def __init__(self):
        self.uploads = 0
        self.readbacks = 0
        self.bytes_up = 0
        self.bytes_down = 0

    def up(self, nbytes: int) -> None:
        self.uploads += 1
        self.bytes_up += int(nbytes)

    def down(self, nbytes: int) -> None:
        self.readbacks += 1
        self.bytes_down += int(nbytes)

    def get_json(self) -> dict:
        return {
            "uploads": self.uploads,
            "readbacks": self.readbacks,
            "bytes_up": self.bytes_up,
            "bytes_down": self.bytes_down,
            "transfers": self.uploads + self.readbacks,
            "bytes_moved": self.bytes_up + self.bytes_down,
        }


class TpuVerifier(BatchVerifier):
    """Batched JAX Ed25519 kernel (ops.ed25519_jax.verify_kernel).

    Batches are padded to power-of-two sizes to bound XLA recompiles.
    ``mesh=`` is the multi-chip width axis (GSPMD stance, Xu et al.
    2021): the batch dimension shards data-parallel over a 1-D device
    mesh of that width (parallel/mesh.py) and XLA splits the whole
    point-arithmetic pipeline across chips over ICI — the production
    integration of SURVEY §2.9 mapping #3 (VERDICT r2 #3). Width 1 and
    width N run the SAME sharded program: there is no separate
    single-device code path, only a narrower mesh.
    """

    name = "tpu"

    def __init__(self, min_batch: int = 256, max_batch: int = 16384,
                 mesh="auto"):
        self.min_batch = min_batch
        self.max_batch = max_batch
        self._kernel = None  # resolved lazily (device discovery)
        self.mesh = parse_mesh(mesh)  # validated at BUILD time, loudly
        self.n_devices = 0  # effective width; set by _resolve_kernel
        self.devices_visible = 0
        self.platform = "unresolved"
        self.kernel_selected = "unresolved"
        # mesh+pallas small-batch bypass (set by _resolve_kernel)
        self._small_kernel = None
        self._mesh_floor = 0
        # Pad policy: "pow2" compiles one XLA program per power-of-two
        # bucket (proportional cost — right when compute scales with the
        # batch, i.e. CPU test backends); "max" pads every chunk to
        # max_batch so exactly ONE program shape ever compiles — right
        # on TPU, where the kernel is latency-flat in batch size (PERF.md
        # round-4 measurements) but every new shape costs a ~60s
        # mid-traffic compile. "auto" (default) picks the platform in
        # _resolve_kernel; until then pow2 is assumed, which only makes
        # the wedge watchdog's first-call deadline conservative.
        env = os.environ.get("STELLARD_PAD_POLICY", "auto")
        if env not in ("auto", "pow2", "max"):
            raise ValueError(
                f"STELLARD_PAD_POLICY={env!r}: expected auto|pow2|max"
            )
        self._pad_policy_env = env
        self.pad_policy = "pow2" if env != "max" else "max"
        self.transfers = TransferMeter()

    def _resolve_kernel(self):
        if self._kernel is not None:
            return self._kernel
        jax = ensure_jax()  # first import may race the hash plane

        from ..parallel.mesh import (
            make_mesh,
            sharded_verify_kernel,
            sharded_verify_kernel_pallas,
        )

        impl = os.environ.get("STELLARD_VERIFY_IMPL", "xla")
        if impl not in ("xla", "pallas"):
            # a perf/debug toggle must not silently no-op (same policy
            # as STELLARD_HOST_VERIFY below)
            raise ValueError(
                f"STELLARD_VERIFY_IMPL={impl!r}: expected 'xla' or 'pallas'"
            )
        devices = jax.devices()
        self.devices_visible = len(devices)
        self.platform = devices[0].platform
        if self._pad_policy_env == "auto":
            self.pad_policy = (
                "max" if devices[0].platform == "tpu" else "pow2"
            )
        # ONE code path at every width (the GSPMD stance): resolve the
        # config axis to an effective width and build the sharded
        # program over a mesh of exactly that many devices — width 1 is
        # a one-device mesh of the same program, not a separate kernel.
        width = resolve_mesh_width(self.mesh, len(devices))
        self.n_devices = width
        mesh = make_mesh(devices[:width])
        if impl == "pallas":
            from ..ops.ed25519_pallas import (
                BLOCK,
                verify_kernel_pallas,
            )

            self._kernel = sharded_verify_kernel_pallas(mesh)
            self.kernel_selected = f"pallas-shardmap@{width}"
            if width > 1:
                # each shard pads itself to a full grid BLOCK, so a
                # batch below width*BLOCK would pay `width` blocks of
                # mostly-zero work for single-block latency; route
                # those to the single-chip kernel instead
                self._small_kernel = verify_kernel_pallas
                self._mesh_floor = width * BLOCK
        else:
            self._kernel = sharded_verify_kernel(mesh)
            self.kernel_selected = f"xla-sharded@{width}"
        # pad floor must divide evenly across the mesh (round UP to a
        # multiple — doubling can never fix an odd device count)
        self.min_batch = ((self.min_batch + width - 1) // width) * width
        return self._kernel

    def describe(self) -> dict:
        """Routing-honesty snapshot: which devices/kernel/width this
        verifier actually resolved to (bench provenance + get_counts
        crypto block)."""
        return {
            "mesh_requested": self.mesh,
            "mesh_width": self.n_devices or None,
            "devices_visible": self.devices_visible or None,
            "platform": self.platform,
            "kernel": self.kernel_selected,
            "pad_policy": self.pad_policy,
            "min_batch": self.min_batch,
            "max_batch": self.max_batch,
        }

    def _pad_size(self, n: int, lo: int, hi: int) -> int:
        if self.pad_policy == "max":
            return hi
        size = lo
        while size < n and size < hi:
            size *= 2
        return size

    def verify_batch(self, batch: Sequence[VerifyRequest]) -> np.ndarray:
        from ..ops.ed25519_jax import prepare_batch

        kernel = self._resolve_kernel()
        starts = list(range(0, len(batch), self.max_batch))

        # double-buffered pipeline: host prep of chunk i+1 overlaps the
        # device execution of chunk i (JAX dispatch is asynchronous)
        out = np.zeros(len(batch), bool)
        pending: list = []  # (start, n, device_future)
        for start in starts:
            chunk = batch[start : start + self.max_batch]
            size = self._pad_size(len(chunk), self.min_batch, self.max_batch)
            nd = self.n_devices
            size = ((size + nd - 1) // nd) * nd  # shardable across the mesh
            pad = size - len(chunk)
            inputs = prepare_batch(
                [r.public for r in chunk] + [b"\x00" * 32] * pad,
                [r.signing_hash for r in chunk] + [b""] * pad,
                [r.signature for r in chunk] + [b"\x00" * 64] * pad,
            )
            k = kernel
            if self._small_kernel is not None and size < self._mesh_floor:
                k = self._small_kernel  # single chip beats 94%-zero shards
            self.transfers.up(sum(v.nbytes for v in inputs.values()))
            res = k(
                inputs["a_words"], inputs["r_words"], inputs["s_windows"],
                inputs["h_digits"], inputs["s_canonical"],
            )
            pending.append((start, len(chunk), res))
            if len(pending) > 1:
                s0, n0, r0 = pending.pop(0)
                got = np.asarray(r0)
                self.transfers.down(got.nbytes)
                out[s0 : s0 + n0] = got[:n0]
        for s0, n0, r0 in pending:
            got = np.asarray(r0)
            self.transfers.down(got.nbytes)
            out[s0 : s0 + n0] = got[:n0]
        return out


class TpuHasher(BatchHasher):
    """Batched JAX SHA-512 (ops.sha512_jax).

    Two paths (VERDICT r2 weak #3):
    - ``prefix_hash_batch``: flat batches, bucketed to a fixed
      block-count ladder and power-of-two batch sizes via the MASKED
      kernel, so the jit cache stays bounded;
    - ``hash_tree``: whole dirty SHAMaps hash level-synchronously with
      device-resident digests — inner payloads are assembled on-device
      by scattering child digests into pre-built templates, every level
      dispatches asynchronously, and the host blocks once at the end.
    """

    name = "tpu"

    def __init__(self, mesh="auto"):
        self.mesh = parse_mesh(mesh)  # validated at BUILD time, loudly
        self.n_devices = 0  # effective width; set on first kernel use
        self.devices_visible = 0
        self.platform = "unresolved"
        self.kernel_selected = "unresolved"
        self._masked = None
        # whole-tree pipeline invocations (hash_tree): device work can
        # be real while the SHARDED flat kernel stays unresolved —
        # provenance must say which one ran
        self.tree_calls = 0
        self._tree_k = None  # (leaf, inner) sharded level kernels
        self.tree_width = 0
        self.tree_kernel = "unresolved"
        self.transfers = TransferMeter()
        # separate meter for the whole-tree pipeline: the residency pin
        # is crisp ONLY here — readbacks == tree_calls (one blocking
        # transfer per tree, never one per level), while the flat path
        # legitimately reads back per bucket
        self.tree_transfers = TransferMeter()

    def prefix_hash_batch(self, prefixes, payloads):
        return self._hash_msgs(
            [p.to_bytes(4, "big") + d for p, d in zip(prefixes, payloads)]
        )

    def hash_packed(self, buf, offsets):
        # packed messages (prefix embedded) slice straight into the
        # device prep — the same single-encoding feed the host path gets
        return self._hash_msgs(
            [buf[offsets[i] : offsets[i + 1]] for i in range(len(offsets) - 1)]
        )

    def _hash_msgs(self, msgs):
        ensure_jax()  # first import may race the verify plane
        import jax.numpy as jnp

        from ..ops.sha512_jax import padded_block_count
        from ..ops.treehash_jax import (
            LEAF_BLOCK_LADDER,
            pad_leaf_batch,
            sha512_blocks_masked,
        )
        from ..utils.hashes import sha512_half

        out: list[bytes | None] = [None] * len(msgs)
        buckets: dict[int, list[int]] = {}
        for i, m in enumerate(msgs):
            nb = padded_block_count(len(m))
            ladder = next((l for l in LEAF_BLOCK_LADDER if nb <= l), None)
            if ladder is None:  # oversized: host path (rare)
                out[i] = sha512_half(m)  # == prefix_hash(prefix, payload)
                self.host_nodes += 1
            else:
                buckets.setdefault(ladder, []).append(i)
                self.device_nodes += 1
        results = []  # (idxs, device_state) — dispatched async, read after
        for ladder, idxs in buckets.items():
            blocks, nblocks = pad_leaf_batch([msgs[i] for i in idxs], ladder)
            self.transfers.up(blocks.nbytes + nblocks.nbytes)
            st = self._masked_kernel()(jnp.asarray(blocks), jnp.asarray(nblocks))
            results.append((idxs, st))
        for idxs, st in results:
            arr = np.asarray(st)  # [Mpad, 16] u32
            self.transfers.down(arr.nbytes)
            raw = arr[:, :8].astype(">u4").tobytes()
            for row, i in enumerate(idxs):
                out[i] = raw[row * 32 : row * 32 + 32]
        return out  # type: ignore[return-value]

    # width -> compiled sharded kernel, shared across instances so the
    # 1-chip and N-chip arms of the three-way routing (and repeated
    # test constructions) never recompile an already-built width
    _KERNELS: dict[int, object] = {}

    def _masked_kernel(self):
        if self._masked is None:
            jax = ensure_jax()  # first import may race the verify plane

            from ..parallel.mesh import make_mesh, sharded_masked_sha512

            devices = jax.devices()
            self.devices_visible = len(devices)
            self.platform = devices[0].platform
            # flat-batch hashing shards data-parallel over the mesh.
            # pow2 widths only, capped at 8: pad_leaf_batch rows are
            # powers of two >= 8, so any power-of-two width up to 8
            # divides them evenly — a non-pow2 mesh= rounds DOWN.
            width = min(
                8, resolve_mesh_width(self.mesh, len(devices), pow2=True)
            )
            self.n_devices = width
            self.kernel_selected = f"masked-sha512-sharded@{width}"
            kern = TpuHasher._KERNELS.get(width)
            if kern is None:
                # one code path at every width: width 1 is a one-device
                # mesh of the same sharded program, not a separate jit
                kern = sharded_masked_sha512(make_mesh(devices[:width]))
                TpuHasher._KERNELS[width] = kern
            self._masked = kern
        return self._masked

    # width -> compiled (leaf, inner) sharded tree-level kernels — the
    # fused close's program set, shared across instances like _KERNELS
    _TREE_KERNELS: dict[int, tuple] = {}

    def _tree_kernels(self):
        if self._tree_k is None:
            jax = ensure_jax()  # first import may race the verify plane

            from ..parallel.mesh import make_mesh, sharded_tree_kernels

            devices = jax.devices()
            self.devices_visible = len(devices)
            self.platform = devices[0].platform
            # same width discipline as the flat kernel: every level's
            # row count is a power of two >= 8, so pow2 widths up to 8
            # divide them evenly at any tree shape
            width = min(
                8, resolve_mesh_width(self.mesh, len(devices), pow2=True)
            )
            self.tree_width = width
            self.tree_kernel = f"tree-sha512-sharded@{width}"
            pair = TpuHasher._TREE_KERNELS.get(width)
            if pair is None:
                # one code path at every width: width 1 is a one-device
                # mesh of the same sharded+donated programs
                pair = sharded_tree_kernels(make_mesh(devices[:width]))
                TpuHasher._TREE_KERNELS[width] = pair
            self._tree_k = pair
        return self._tree_k

    def describe(self) -> dict:
        """Routing-honesty snapshot (bench provenance / get_counts).
        `kernel`/`mesh_width` describe the SHARDED flat-batch kernel;
        `tree_kernel`/`tree_width` the fused whole-tree program set and
        `tree_pipeline_calls` its run count — either arm can carry the
        device traffic while the other stays unresolved, and provenance
        must say which one ran."""
        return {
            "mesh_requested": self.mesh,
            "mesh_width": self.n_devices or None,
            "devices_visible": self.devices_visible or None,
            "platform": self.platform,
            "kernel": self.kernel_selected,
            "tree_kernel": self.tree_kernel,
            "tree_width": self.tree_width or None,
            "tree_pipeline_calls": self.tree_calls,
            "transfers": self.transfers.get_json(),
            "tree_transfers": self.tree_transfers.get_json(),
        }

    # -- whole-tree pipeline ----------------------------------------------

    def hash_tree(self, root, cancelled=None, cancel_lock=None) -> int:
        """Fill every missing node hash in a SHAMap with device-resident
        level-synchronous hashing. Returns the number of nodes hashed.

        ``cancelled``/``cancel_lock`` (threading.Event/Lock, optional,
        supplied together by the watchdog — utils.devicewatch): the
        write-back runs check-then-stamp as ONE critical section under
        ``cancel_lock``, and the watchdog sets ``cancelled`` under the
        same lock before it starts any host fallback. Either this call
        stamps the whole tree before the fallback begins, or it stamps
        nothing — an abandoned (zombie) call can never interleave writes
        with the fallback's traversal."""
        ensure_jax()  # first import may race the verify plane
        import jax.numpy as jnp

        from ..ops.sha512_jax import padded_block_count
        from ..ops.treehash_jax import (
            INNER_WORDS,
            LEAF_BLOCK_LADDER,
            build_inner_template,
            pad_leaf_batch,
            _pow2,
        )
        from ..state.shamap import (
            Inner,
            Leaf,
            ZERO256,
            _collect_unhashed,
            encode_nodes,
        )
        from ..utils.hashes import HP_INNER_NODE, sha512_half

        levels = _collect_unhashed(root)
        if not levels:
            return 0

        index_of: dict[int, int] = {}  # id(node) -> digest-buffer row
        plan: list[tuple] = []
        offset = 0
        hashed_host = 0

        for level in reversed(levels):
            leaves_by_bucket: dict[int, list] = {}
            inners: list = []
            leaves: list = []
            for node in level:
                if isinstance(node, Leaf):
                    leaves.append(node)
                elif node.is_empty():
                    node._hash = ZERO256
                    hashed_host += 1
                else:
                    inners.append(node)
            if leaves:
                # one flat-buffer encoding feeds the whole level's device
                # prep (the same encoder the host SHA batch consumes)
                lbuf, loff = encode_nodes(leaves)
                for i, node in enumerate(leaves):
                    msg = lbuf[loff[i] : loff[i + 1]]
                    nb = padded_block_count(len(msg))
                    ladder = next(
                        (l for l in LEAF_BLOCK_LADDER if nb <= l), None
                    )
                    if ladder is None:  # oversized leaf: host hash, known
                        node._hash = sha512_half(msg)
                        hashed_host += 1
                    else:
                        leaves_by_bucket.setdefault(ladder, []).append(
                            (node, msg)
                        )
            for ladder, entries in sorted(leaves_by_bucket.items()):
                for i, (node, _msg) in enumerate(entries):
                    index_of[id(node)] = offset + i
                plan.append(("leaf", ladder, entries, offset))
                offset += _pow2(len(entries))
            if inners:
                for i, node in enumerate(inners):
                    index_of[id(node)] = offset + i
                plan.append(("inner", inners, offset))
                offset += _pow2(len(inners))

        if not plan:
            self.host_nodes += hashed_host
            return hashed_host

        # counted HERE, not at entry: tree_calls must pair 1:1 with the
        # pipeline's single readback (the residency pin readbacks ==
        # tree_calls), so already-hashed / host-only calls don't count
        self.tree_calls += 1
        cap = _pow2(offset)
        # the persistent device buffer: every level kernel takes it
        # DONATED and hands back the same allocation, so the whole
        # chain runs device-resident at any mesh width
        leaf_k, inner_k = self._tree_kernels()
        buf = jnp.zeros((cap, 8), jnp.uint32)
        prefix_words = int(HP_INNER_NODE)

        for step in plan:
            if step[0] == "leaf":
                _k, ladder, entries, off = step
                blocks, nblocks = pad_leaf_batch(
                    [msg for _n, msg in entries], ladder
                )
                self.tree_transfers.up(blocks.nbytes + nblocks.nbytes)
                buf = leaf_k(
                    buf, jnp.asarray(blocks), jnp.asarray(nblocks), off
                )
            else:
                _k, inners, off = step
                n = len(inners)
                template = build_inner_template(n, pow2_rows=True)
                template[:, 0] = prefix_words
                rows, col_base, src_rows = [], [], []
                for i, node in enumerate(inners):
                    for c, child in enumerate(node.children):
                        if child is None:
                            h = ZERO256
                        elif child._hash is not None:
                            h = child._hash
                        else:
                            rows.append(i)
                            col_base.append(1 + 8 * c)
                            src_rows.append(index_of[id(child)])
                            continue
                        template[i, 1 + 8 * c : 9 + 8 * c] = np.frombuffer(
                            h, dtype=">u4"
                        )
                if rows:
                    # quantize the scatter program to a pow2 length by
                    # REPEATING entry 0 — duplicate scatters of one
                    # identical (index, value) are deterministic, so no
                    # scratch row is needed and template rows stay
                    # pow2/mesh-divisible ([0]-length programs when
                    # every child hash is already known)
                    pad = _pow2(len(rows)) - len(rows)
                    rows += [rows[0]] * pad
                    col_base += [col_base[0]] * pad
                    src_rows += [src_rows[0]] * pad
                ra = np.array(rows, np.int32)
                ca = np.array(col_base, np.int32)
                sa = np.array(src_rows, np.int32)
                self.tree_transfers.up(
                    template.nbytes + ra.nbytes + ca.nbytes + sa.nbytes
                )
                buf = inner_k(
                    buf,
                    jnp.asarray(template),
                    jnp.asarray(ra),
                    jnp.asarray(ca),
                    jnp.asarray(sa),
                    off,
                )

        host = np.asarray(buf)  # ONE transfer; blocks on the whole chain
        self.tree_transfers.down(host.nbytes)
        lock = cancel_lock if cancel_lock is not None else threading.Lock()
        with lock:
            if cancelled is not None and cancelled.is_set():
                return 0  # abandoned by the watchdog: tree untouched
            raw = host.astype(">u4").tobytes()
            for level in levels:
                for node in level:
                    if node._hash is None:
                        row = index_of[id(node)]
                        node._hash = raw[row * 32 : row * 32 + 32]
        self.host_nodes += hashed_host
        self.device_nodes += len(index_of)
        return hashed_host + len(index_of)


register_verifier("cpu", _host_verifier_factory, options=("threads",))
# strict: raises if unbuildable
register_verifier("native", NativeVerifier, options=())
# always-available host library
register_verifier("openssl", CpuVerifier, options=("threads",))
register_verifier("tpu", TpuVerifier,
                  options=("min_batch", "max_batch", "mesh"))
register_hasher("cpu", CpuHasher, options=())
register_hasher("tpu", TpuHasher, options=("mesh",))


class CppHasher(BatchHasher):
    """Native batched SHA-512-half (native/src/sha512.cc) — one C call
    per batch, filling the reference's OpenSSL-hashing role for the host
    path when the device hasher isn't warranted."""

    name = "cpp"

    def __init__(self, **_):
        from ..native import Sha512Native

        self._impl = Sha512Native()

    def prefix_hash_batch(self, prefixes, payloads):
        self.host_nodes += len(prefixes)
        return self._impl.prefix_hash_batch(prefixes, payloads)

    def hash_packed(self, buf, offsets):
        # the flat-buffer seal path: ONE buffer + offsets array into C,
        # no per-node join/slice on the Python side
        self.host_nodes += max(0, len(offsets) - 1)
        return self._impl.hash_packed(buf, offsets)


# registered unconditionally: CppHasher.__init__ raises a clean error on
# a toolchain-less box, and the (one-time) native build cost lands only
# on callers that actually select the cpp backend — never at import
register_hasher("cpp", CppHasher, options=())


class _RoutedFlat:
    """Flat-batch facade over a WatchdogHasher for compute_hashes: the
    routed/watchdogged prefix+packed paths WITHOUT the hash_tree attr
    (which would recurse back into the watchdog's tree dispatch)."""

    __slots__ = ("_wd",)

    def __init__(self, wd: "WatchdogHasher"):
        self._wd = wd

    def __call__(self, prefixes, payloads):
        return self._wd.prefix_hash_batch(prefixes, payloads)

    def prefix_hash_batch(self, prefixes, payloads):
        return self._wd.prefix_hash_batch(prefixes, payloads)

    def hash_packed(self, buf, offsets):
        return self._wd.hash_packed(buf, offsets)

    # the watchdog's routing counters, for a caller that reports which
    # arm took its batch (the shard contract, nodestore/shards.py)
    @property
    def device_nodes(self) -> int:
        return self._wd.device_nodes

    @property
    def host_nodes(self) -> int:
        return self._wd.host_nodes


# flat batches below this never route to a device backend: a handful of
# residual nodes can never amortize a device round-trip (the incremental
# seal's drain leftovers are the motivating case). [hash_backend]
# min_device_nodes= overrides it.
DEVICE_HASH_FLOOR = 64


def make_watched_hasher(backend: str,
                        min_device_nodes: Optional[int] = None,
                        mesh=None,
                        routing: str = "cost",
                        first_timeout: Optional[float] = None,
                        ) -> BatchHasher:
    """The ONE wiring for a possibly-device hasher: the tpu backend is
    wrapped in the wedge watchdog with a cpu fallback (a hung device
    call must degrade, not freeze) and the small-batch device floor; host
    backends pass through untouched.

    ``mesh`` is the [hash_backend] width axis (parse_mesh values). When
    it requests more than one chip, the watchdog gets BOTH a wide inner
    and a width-1 inner — the N-chip and 1-chip arms of the three-way
    measured-cost routing (host / 1-chip / N-chip), so small batches
    stay on host, medium batches on one chip, and only batches that
    amortize the collective go wide. ``routing`` is "cost" or "device";
    ``first_timeout`` the wedge deadline; ``min_device_nodes`` None
    means DEVICE_HASH_FLOOR."""
    opts = {}
    if backend == "tpu" and mesh is not None:
        opts["mesh"] = mesh
    hasher = make_hasher(backend, **opts)
    if backend == "tpu":
        floor = min_device_nodes
        if floor is None:
            floor = DEVICE_HASH_FLOOR
        inner_one = None
        if mesh_wants_width(mesh if mesh is not None else "auto"):
            # the 1-chip arm: the SAME sharded program at width 1
            inner_one = make_hasher("tpu", mesh="0")
        hasher = WatchdogHasher(
            hasher, make_hasher("cpu"), min_device_nodes=floor,
            inner_one=inner_one, routing=routing,
            first_timeout=first_timeout,
        )
    return hasher


class _HashCostModel:
    """Measured-cost routing for the hash plane (the VerifyPlane
    stance), generalized from host-vs-device to host + N device ARMS
    (the three-way host / 1-chip / N-chip split): per-pow2-bucket
    EWMAs per arm, first (compile-laden) sample discarded per
    (arm, bucket), one host measurement enables the comparison, and a
    losing arm re-explores per (arm, bucket) after `reexplore_every`
    eligible losses (a counter, not a global modulo — a bucket whose
    calls never align with a global stride must not be starved),
    bounded to within 4x of the winning cost. Thread-safe: the hasher
    is shared across node threads."""

    EWMA = 0.3
    REEXPLORE_BOUND = 4.0

    def __init__(self, reexplore_every: int, min_device_nodes: int = 0,
                 arms: Sequence[str] = ("device",)):
        self._lock = threading.Lock()
        self._reexplore = reexplore_every
        # floor knob: batches below this size NEVER route to (or explore)
        # the device — the incremental seal's residual batches are a few
        # nodes, far below any plausible device win, and without the
        # floor every tiny residual would re-trigger per-bucket
        # exploration (a device round-trip per close)
        self.min_device_nodes = max(0, int(min_device_nodes))
        self.arms = tuple(arms)
        # arm -> bucket -> [n_samples, ewma]
        self._dev: dict[str, dict[int, list]] = {a: {} for a in self.arms}
        self._host_unit_ms: Optional[float] = None
        self._losses: dict[tuple[str, int], int] = {}

    @staticmethod
    def _bucket(n: int) -> int:
        return 1 << max(0, n - 1).bit_length()

    def _ewma(self, cur: Optional[float], ms: float) -> float:
        return ms if cur is None else (1 - self.EWMA) * cur + self.EWMA * ms

    def get_json(self) -> dict:
        """Routing-model snapshot (the get_counts crypto block).
        `buckets` keeps the legacy
        single-arm view (the primary device arm); `arms` is the full
        three-way snapshot."""
        with self._lock:
            arms = {
                arm: {
                    str(b): {"samples": s[0], "ewma_ms": s[1]}
                    for b, s in sorted(slots.items())
                }
                for arm, slots in self._dev.items()
            }
            return {
                "min_device_nodes": self.min_device_nodes,
                "host_unit_ms": self._host_unit_ms,
                "arms": arms,
                # legacy single-arm view: the PRIMARY (widest) arm —
                # the one that keeps accumulating after arm collapse,
                # matching _LatencyModel's device_bucket_ms view
                "buckets": arms[self.arms[-1]],
                "losses": {
                    f"{a}:{b}": v
                    for (a, b), v in sorted(self._losses.items())
                },
            }

    def choose(self, n: int, arms: Optional[Sequence[str]] = None) -> str:
        """Pick the arm for an n-node batch: ``"host"`` or a device arm
        name. Unmeasured device arms are explored first (in declared
        order); the host is measured once before any comparison; after
        that the cheapest measured arm wins, with bounded per-(arm,
        bucket) re-exploration of close losers."""
        avail = [a for a in (arms if arms is not None else self.arms)
                 if a in self._dev]
        with self._lock:
            if n < self.min_device_nodes or not avail:
                return "host"  # below any plausible win size
            b = self._bucket(n)
            costs: dict[str, float] = {}
            for a in avail:
                slot = self._dev[a].setdefault(b, [0, None])
                if slot[1] is None:
                    return a  # unmeasured (or compile sample): explore
                costs[a] = slot[1]
            if self._host_unit_ms is None:
                return "host"  # measure the host side once
            exp_host = self._host_unit_ms * n
            best_arm = min(costs, key=lambda a: costs[a])
            if costs[best_arm] <= exp_host:
                self._losses.pop((best_arm, b), None)
                winner, best = best_arm, costs[best_arm]
            else:
                winner, best = "host", exp_host
            # losing device arms within striking distance of the winner
            # accrue losses and periodically re-explore; hopeless arms
            # (beyond the 4x band) never do
            for a in avail:
                if a == winner:
                    continue
                if costs[a] > self.REEXPLORE_BOUND * best:
                    continue
                k = (a, b)
                self._losses[k] = self._losses.get(k, 0) + 1
                if self._losses[k] >= self._reexplore:
                    self._losses[k] = 0
                    return a
            return winner

    def use_device(self, n: int) -> bool:
        return self.choose(n) != "host"

    def observe(self, arm: str, n: int, ms: float) -> None:
        if arm == "host":
            with self._lock:
                self._host_unit_ms = self._ewma(self._host_unit_ms, ms / n)
            return
        with self._lock:
            slot = self._dev[arm].setdefault(self._bucket(n), [0, None])
            slot[0] += 1
            if slot[0] <= 1:
                return  # discard the compile-laden first sample
            slot[1] = self._ewma(slot[1], ms)

    # legacy single-arm shims (tests / two-way callers): the primary
    # arm is the WIDEST, same as the get_json "buckets" view
    def observe_device(self, n: int, ms: float) -> None:
        self.observe(self.arms[-1], n, ms)

    def observe_host(self, n: int, ms: float) -> None:
        self.observe("host", n, ms)


class WatchdogHasher(BatchHasher):
    """Run a device hasher's calls under a wedge deadline with a CPU
    fallback (utils.devicewatch): a device call that never returns
    would freeze the tree-hash, and with it every ledger close. One
    overrun routes hashing to the fallback for the life of
    the process (sticky, shared with the verify plane's verdict).

    Deadlines: every hashing call gets the GENEROUS compile deadline.
    Unlike the verify plane (whose pad-bucket set is enumerable, so
    warmth is provable per shape), the device hasher compiles one
    program per (padded-batch, block-ladder) combination and tree
    hashing per level size — none of which the wrapper can enumerate
    from outside, so no call is provably recompile-free and a tight
    deadline would falsely kill a healthy device mid-compile. Hashing
    sits off the latency-critical path (closes batch it), and the
    verify plane's tight warmed deadline still provides fast wedge
    detection for the shared process-wide verdict.
    """

    # [tree] fused kill-switch surface: node.py stamps cfg.tree_fused
    # here, and shamap.compute_hashes / ledgermaster._drain_loop consult
    # it before taking the whole-tree device pipeline (fused=0 keeps
    # the staged per-level hash_packed path — the identity leg)
    fused_enabled = True

    def __init__(self, inner: BatchHasher, fallback: BatchHasher,
                 first_timeout: Optional[float] = None,
                 warm_timeout: Optional[float] = None,
                 min_device_nodes: int = 0,
                 inner_one: Optional[BatchHasher] = None,
                 routing: str = "cost"):
        from ..utils.devicewatch import resolve_timeouts

        self.inner = inner
        self.fallback = fallback
        # the 1-chip arm of the three-way routing: the same device
        # program at mesh width 1 (make_watched_hasher builds it when
        # [hash_backend] mesh= requests more than one chip). None keeps
        # the classic two-way host/device split.
        self.inner_one = inner_one
        self.name = inner.name
        self._t_first, _ = resolve_timeouts(first_timeout, warm_timeout)
        self.device_wedged = False
        # measured-cost routing (same stance as VerifyPlane's model: the
        # device must EARN traffic; a losing device floors at the host
        # path instead of dragging a leg, and is re-explored bounded).
        # routing="device" restores route-everything-device — the
        # widest arm.
        # (A separate small model rather than verifyplane._LatencyModel:
        # the units differ — per-node hash rates vs per-signature verify
        # costs — and the verify model is entangled with pad-bucket
        # warmth bookkeeping this wrapper has no analog for.)
        if routing not in ("cost", "device"):
            raise ValueError(
                f"hash routing must be cost|device, got {routing!r}"
            )
        self.routing = routing
        self._route_by_cost = routing != "device"
        # device floor: flat batches below this size never route to the
        # device, and tree hashing with a caller-supplied dirty-count
        # hint below it goes straight to the host level-batcher — the
        # incremental seal's residuals must not burn a device round-trip
        # per close. Default 0 (a watchdog wrapped around a HOST inner —
        # the test harness shape — must not divert its inner's traffic);
        # make_watched_hasher applies the device-backend default.
        floor = int(min_device_nodes)
        if floor < 0:
            raise ValueError(f"min_device_nodes must be >= 0, got {floor}")
        self.min_device_nodes = floor
        self._arm_names = (
            ("dev1", "devN") if inner_one is not None else ("device",)
        )
        self._flat = _HashCostModel(
            reexplore_every=256, min_device_nodes=floor,
            arms=self._arm_names,
        )
        # tree model buckets per-node RATE in the size-independent
        # bucket 1 — the floor applies via the hash_tree hint, not here
        # (the whole-tree device pipeline is a single-program scatter
        # chain, so it stays a two-way host/device decision)
        self._tree = _HashCostModel(reexplore_every=64)

    def _live_arms(self) -> tuple:
        """The device arms currently worth routing between. Once the
        wide inner RESOLVES to a single device (mesh= wider than the
        box), the 1-chip arm is the identical program — collapse it so
        the model stops exploring a duplicate."""
        if (self.inner_one is not None
                and getattr(self.inner, "n_devices", 0) == 1):
            self.inner_one = None
        if self.inner_one is None and len(self._arm_names) > 1:
            return self._arm_names[-1:]
        return self._arm_names

    def _inner_of(self, arm: str) -> BatchHasher:
        if arm == "dev1" and self.inner_one is not None:
            return self.inner_one
        return self.inner

    @property
    def device_nodes(self):  # type: ignore[override]
        one = self.inner_one.device_nodes if self.inner_one is not None else 0
        return self.inner.device_nodes + one

    @device_nodes.setter
    def device_nodes(self, value):  # counter reset (bench legs)
        self.inner.device_nodes = value
        if self.inner_one is not None:
            self.inner_one.device_nodes = 0

    @property
    def host_nodes(self):  # type: ignore[override]
        one = self.inner_one.host_nodes if self.inner_one is not None else 0
        return self.inner.host_nodes + self.fallback.host_nodes + one

    @host_nodes.setter
    def host_nodes(self, value):  # counter reset (bench legs)
        # round-trips: getter sums inner + fallback, so the value goes
        # to inner and the other shares zero
        self.inner.host_nodes = value
        self.fallback.host_nodes = 0
        if self.inner_one is not None:
            self.inner_one.host_nodes = 0

    def _wedge(self, exc: Exception) -> None:
        from ..utils.devicewatch import log as dlog

        self.device_wedged = True
        dlog.error("hash plane: %s — falling back to host hashing", exc)

    def prefix_hash_batch(self, prefixes, payloads):
        return self._routed(
            len(prefixes),
            lambda arm: self._inner_of(arm).prefix_hash_batch(
                prefixes, payloads
            ),
            lambda: self.fallback.prefix_hash_batch(prefixes, payloads),
        )

    def hash_packed(self, buf, offsets):
        """Routed flat-buffer hashing (the seal/flush path): same cost
        model and wedge watchdog as the (prefix, payload) shape."""
        return self._routed(
            len(offsets) - 1,
            lambda arm: self._inner_of(arm).hash_packed(buf, offsets),
            lambda: self.fallback.hash_packed(buf, offsets),
        )

    def _routed(self, n, device_call, host_fn):
        """Three-way measured-cost dispatch: host / 1-chip / N-chip.
        ``device_call(arm)`` runs the batch on that arm's inner hasher;
        cost-mode picks the cheapest measured arm (exploring unmeasured
        ones), device-mode forces the widest arm."""
        import time as _t

        from ..utils.devicewatch import DeviceWedged, call_with_deadline

        arm: Optional[str] = None
        if not self.device_wedged and n > 0:
            if not self._route_by_cost:
                arm = self._live_arms()[-1]  # forced: the widest arm
            else:
                choice = self._flat.choose(n, arms=self._live_arms())
                arm = None if choice == "host" else choice
        if arm is not None:
            try:
                t0 = _t.perf_counter()
                out = call_with_deadline(
                    lambda: device_call(arm), self._t_first,
                    label="hash-device",
                )
                self._flat.observe(
                    arm, n, (_t.perf_counter() - t0) * 1000.0
                )
                return out
            except DeviceWedged as exc:
                self._wedge(exc)
        t0 = _t.perf_counter()
        out = host_fn()
        if n > 0:
            self._flat.observe(
                "host", n, (_t.perf_counter() - t0) * 1000.0
            )
        return out

    def get_json(self) -> dict:
        """Hash-plane routing snapshot (bench legs record it next to
        device_share so a routed-out device is self-explaining): mesh
        width/kernel per arm plus the three-arm cost-model state."""
        describe = getattr(self.inner, "describe", None)
        return {
            "backend": self.name,
            "wedged": self.device_wedged,
            "routing": self.routing,
            "arms": list(self._live_arms()),
            "fused": bool(self.fused_enabled),
            "mesh": describe() if describe is not None else None,
            "device_nodes": self.device_nodes,
            "host_nodes": self.host_nodes,
            "min_device_nodes": self.min_device_nodes,
            "transfers": self.transfer_json(),
            "flat_model": self._flat.get_json(),
            "tree_model": self._tree.get_json(),
        }

    def transfer_json(self) -> Optional[dict]:
        """Transfer-honesty aggregate over both device arms (the N-chip
        inner and the 1-chip arm when present): per-close deltas of this
        block are the residency proof — a fused close moves ONE readback
        per tree, not one per level."""
        agg: Optional[dict] = None
        for h in (self.inner, self.inner_one):
            if h is None:
                continue
            # both meters per arm: the flat hash_packed meter AND the
            # whole-tree pipeline meter (split so the one-readback pin
            # stays crisp on tree_transfers alone)
            for meter in (getattr(h, "transfers", None),
                          getattr(h, "tree_transfers", None)):
                if meter is None:
                    continue
                j = meter.get_json()
                if agg is None:
                    agg = dict(j)
                else:
                    for k, v in j.items():
                        agg[k] = agg.get(k, 0) + v
        return agg

    def flat_hasher(self) -> "_RoutedFlat":
        """This hasher's routed FLAT facade (no hash_tree attr): tree
        hashing through it level-batches per-level pack_nodes buffers
        into the routed hash_packed path — the sharded masked-SHA
        kernel under device routing. The scenario plane uses it so
        chaos runs exercise the SHARDED flat plane, not the unsharded
        whole-tree scatter pipeline."""
        return _RoutedFlat(self)

    def _host_tree(self, root) -> int:
        """Level-batched host hashing. When the device is healthy this
        still routes through the WATCHED flat path (so e.g. a native
        cpp inner without hash_tree is used, watchdogged, for the
        dominant tree workload); once wedged it goes straight to the
        fallback."""
        from ..state.shamap import compute_hashes

        if self.device_wedged:
            return compute_hashes(root, self.fallback)
        return compute_hashes(root, _RoutedFlat(self))

    def hash_tree(self, root, hint_nodes: Optional[int] = None) -> int:
        import time as _t

        from ..utils.devicewatch import DeviceWedged, call_with_deadline

        inner_tree = getattr(self.inner, "hash_tree", None)
        if inner_tree is None:
            return self._host_tree(root)
        if (
            hint_nodes is not None
            and hint_nodes < self.min_device_nodes
            and self._route_by_cost
        ):
            # caller-declared small dirty set (incremental-seal residual
            # drains): below any plausible device win, and exploring the
            # device per tiny batch would burn a round-trip per close
            return self._host_tree(root)
        if not self.device_wedged and self._route_by_cost and (
            not self._tree.use_device(1)
        ):
            from ..state.shamap import compute_hashes

            t0 = _t.perf_counter()
            count = compute_hashes(root, self.fallback)
            if count:
                self._tree.observe_host(
                    count, (_t.perf_counter() - t0) * 1000.0
                )
            return count
        if not self.device_wedged:
            import inspect

            params = inspect.signature(inner_tree).parameters
            cancel = threading.Event() if "cancelled" in params else None
            lock = threading.Lock() if "cancel_lock" in params else None
            kwargs = {}
            if cancel is not None:
                kwargs["cancelled"] = cancel
            if lock is not None:
                kwargs["cancel_lock"] = lock
            try:
                t0 = _t.perf_counter()
                count = call_with_deadline(
                    lambda: inner_tree(root, **kwargs), self._t_first,
                    label="hash-device",
                )
                if count:
                    # per-node rate in the size-independent bucket 1
                    self._tree.observe_device(
                        1, (_t.perf_counter() - t0) * 1000.0 / count
                    )
                return count
            except DeviceWedged as exc:
                # Close the zombie race BEFORE any host work touches the
                # tree: setting cancelled under the shared lock means the
                # abandoned call either already stamped the whole tree
                # (its critical section completed first — the fallback
                # then finds nothing to hash) or will stamp nothing.
                if cancel is not None:
                    if lock is not None:
                        with lock:
                            cancel.set()
                    else:
                        cancel.set()
                self._wedge(exc)
        return self._host_tree(root)


# --------------------------------------------------------------------------
# path-quality plane: measured-cost routed Q16.16 candidate evaluation

# candidate batches below this never route to a device: a path_find with
# a handful of candidates can never amortize a dispatch (the sig/hash
# planes' DEVICE_*_FLOOR stance). [paths] min_device_batch= overrides it.
PATHQ_DEVICE_FLOOR = 256


class PathQualityEvaluator:
    """Routed evaluation of flattened candidate-path rate matrices (the
    liquidity plane's device arm — ISSUE 17 tentpole leg 3).

    Same construction as the sig/hash planes: a NumPy host arm
    (ops.pathq_jax.path_quality_host), a 1-chip arm and an optional
    N-chip arm of the SAME sharded jit program
    (parallel.mesh.sharded_path_quality), routed per batch by the
    shared measured-cost model (_HashCostModel: per-pow2-bucket EWMAs,
    compile-sample discard, bounded re-exploration, small-batch host
    floor). Host and device arms are byte-identical at every mesh
    width — pinned by tests/test_path_plane.py and the bench leg.

    ``routing``: "cost" (default) measures; "device" forces the widest
    device arm (identity pinning / bench anti-vacuity); "host" forces
    the host arm.
    """

    def __init__(self, mesh=None,
                 min_device_batch: int = PATHQ_DEVICE_FLOOR,
                 routing: str = "cost"):
        self.mesh = parse_mesh(mesh)
        routing = routing.strip().lower()
        if routing not in ("cost", "device", "host"):
            raise ValueError(
                f"path evaluator routing must be cost|device|host, "
                f"got {routing!r}"
            )
        self.routing = routing
        arms = ("dev1", "devN") if mesh_wants_width(self.mesh) else ("dev1",)
        self._model = _HashCostModel(
            reexplore_every=64, min_device_nodes=min_device_batch, arms=arms,
        )
        self._lock = threading.Lock()
        self._kernels: dict[str, tuple] = {}  # arm -> (jit fn, width)
        self.host_batches = 0
        self.device_batches = 0
        self.rows_evaluated = 0

    # -- arms -------------------------------------------------------------

    def _kernel(self, arm: str):
        with self._lock:
            hit = self._kernels.get(arm)
            if hit is not None:
                return hit
        jax = ensure_jax()
        from ..parallel.mesh import make_mesh, sharded_path_quality

        devices = jax.devices()
        want = "0" if arm == "dev1" else self.mesh
        width = resolve_mesh_width(want, len(devices), pow2=True)
        fn = sharded_path_quality(make_mesh(devices[:width]))
        with self._lock:
            self._kernels.setdefault(arm, (fn, width))
            return self._kernels[arm]

    def evaluate_host(self, rates: np.ndarray) -> np.ndarray:
        from ..ops.pathq_jax import path_quality_host

        return path_quality_host(rates)

    def _evaluate_device(self, arm: str, rates: np.ndarray) -> np.ndarray:
        from ..ops.pathq_jax import Q16_ONE

        fn, width = self._kernel(arm)
        n = rates.shape[0]
        # pow2 padding (identity rows): one compile per bucket, and any
        # pow2 width divides the padded batch for the sharded program
        padded = max(width, 1 << max(0, n - 1).bit_length())
        if padded != n:
            pad = np.full((padded - n, rates.shape[1]), Q16_ONE,
                          dtype=np.uint32)
            rates = np.concatenate([rates, pad], axis=0)
        out = np.asarray(fn(rates))
        return out[:n]

    # -- routed entry point ----------------------------------------------

    def evaluate(self, rates: np.ndarray) -> np.ndarray:
        """[B, H] uint32 -> [B] uint32 composites, routed host/1-chip/
        N-chip by measured cost (or forced by ``routing``)."""
        import time as _t

        rates = np.ascontiguousarray(rates, dtype=np.uint32)
        n = int(rates.shape[0])
        if n == 0:
            return np.zeros((0,), dtype=np.uint32)
        if self.routing == "host":
            arm = "host"
        elif self.routing == "device":
            arm = self._model.arms[-1]
        else:
            arm = self._model.choose(n)
        t0 = _t.perf_counter()
        if arm == "host":
            out = self.evaluate_host(rates)
        else:
            out = self._evaluate_device(arm, rates)
        self._model.observe(arm, n, (_t.perf_counter() - t0) * 1000.0)
        with self._lock:
            self.rows_evaluated += n
            if arm == "host":
                self.host_batches += 1
            else:
                self.device_batches += 1
        return out

    def device_width(self) -> int:
        """Effective width of the widest device arm (builds it)."""
        return self._kernel(self._model.arms[-1])[1]

    def get_json(self) -> dict:
        with self._lock:
            widths = {a: w for a, (_f, w) in self._kernels.items()}
            counters = {
                "host_batches": self.host_batches,
                "device_batches": self.device_batches,
                "rows_evaluated": self.rows_evaluated,
            }
        return {
            "mesh": self.mesh,
            "routing": self.routing,
            "min_device_batch": self._model.min_device_nodes,
            "arm_widths": widths,
            **counters,
            "model": self._model.get_json(),
        }
