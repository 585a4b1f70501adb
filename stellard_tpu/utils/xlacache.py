"""Shared persistent XLA compilation cache setup, and the compile meter.

The big kernels (batched Ed25519 verify, tree hashing) take minutes to
compile for the CPU backend and tens of seconds for TPU; one on-disk cache
makes every process after the first fast. ``crypto.backend.ensure_jax``
calls ``enable_compilation_cache`` so every device entry point gets it;
tests/conftest.py and the tools that import jax themselves call it too.

Placement rule: where ``JAX_COMPILATION_CACHE_DIR`` is set the operator
owns the location — JAX reads that variable itself and this module sets
no directory in code. Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache/<cpu-fingerprint>`` (the path is part of the
cache's usefulness: a directory that moves never hits).

The fingerprint subdirectory is a function of /proc/cpuinfo only: XLA:CPU
AOT blobs encode the compiling machine's ISA features, and replaying a
foreign blob can SIGILL an unattended run (or at best spam the
machine-feature-mismatch warning every replay). A box with different CPU
features simply gets its own subdirectory and recompiles once.
"""

from __future__ import annotations

import hashlib
import os
import platform
import threading

# Programs that compile faster than this stay out of the cache. The tree
# plane compiles one program per (buffer capacity, padded rows, ladder,
# scatter length) combination: 128 of them over a 16-close flood on the
# v5e, 0.2-0.5 s each (PERF.md, "On the chip, PR 21"). At the former
# floor of 2 s none was kept, so a second process recompiled every one
# it met; 0.1 s keeps them and still leaves out the sub-100 ms programs
# (zeros, converts) that cost less to build than to look up.
MIN_COMPILE_TIME_SECS = 0.1

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def host_cpu_fingerprint() -> str:
    """Short stable digest of the host's CPU feature set (ISA flags +
    machine arch). Two hosts share a cache subdir only when an AOT blob
    compiled on one is guaranteed executable on the other."""
    feats = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    # one flags line suffices; identical across cores
                    feats = " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        pass
    if not feats:
        feats = platform.processor() or "unknown"
    key = f"{platform.machine()}|{feats}"
    return hashlib.sha256(key.encode()).hexdigest()[:12]


class CompileMeter:
    """Process-wide count of XLA compile requests, by program name.

    JAX reports every executable it builds as one
    ``backend_compile_duration`` event carrying the jitted function's
    name; a persistent-cache hit fires ``cache_hits`` on the same thread
    just before it. ``requests - cache_hits`` is therefore the number of
    programs the compiler really built in this process, and ``seconds``
    the wall time spent building or loading them. A benchmark snapshots
    this around its timed window (the target there is zero); the chip
    smoke uses it to prove a second process loads the verify program
    from the cache instead of compiling it."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._tls = threading.local()
        # name -> [requests, cache_hits, seconds]
        self._programs: dict[str, list] = {}
        self._installed = False
        # called as fn(name, cache_hit, seconds) when a program has been
        # built or loaded, on the thread that asked for it (the verify
        # prewarm records its `prewarm.program` spans through this)
        self.observers: list = []

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT_EVENT:
            self._tls.hit = True

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event != _BACKEND_COMPILE_EVENT:
            return
        hit = getattr(self._tls, "hit", False)
        self._tls.hit = False
        name = str(kw.get("fun_name", "?"))
        with self._lock:
            slot = self._programs.setdefault(name, [0, 0, 0.0])
            slot[0] += 1
            slot[1] += 1 if hit else 0
            slot[2] += float(secs)
        for fn in list(self.observers):
            fn(name, hit, float(secs))

    def install(self) -> None:
        """Register the listeners (once; jax keeps them for the life of
        the process)."""
        from jax import monitoring

        with self._lock:
            if self._installed:
                return
            self._installed = True
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def snapshot(self) -> dict:
        with self._lock:
            programs = {
                name: {"requests": s[0], "cache_hits": s[1],
                       "seconds": round(s[2], 3)}
                for name, s in sorted(self._programs.items())
            }
        requests = sum(p["requests"] for p in programs.values())
        hits = sum(p["cache_hits"] for p in programs.values())
        return {
            "requests": requests,
            "cache_hits": hits,
            "compiled": requests - hits,
            "seconds": round(sum(p["seconds"] for p in programs.values()), 3),
            "programs": programs,
        }


# one meter per process, like the cache it watches (jax.monitoring
# listeners are process-global and the compile cache is too)
COMPILES = CompileMeter()


def enable_compilation_cache() -> str:
    """Enable JAX's persistent compilation cache (see the module
    docstring for where it lives) and install the compile meter. Safe
    to call more than once. Returns the directory in use."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        cache_dir = os.path.join(
            pkg_root, ".jax_cache", host_cpu_fingerprint()
        )
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # else: placed from outside — JAX has already read the variable
    # into its config, and this module sets no directory
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", MIN_COMPILE_TIME_SECS
    )
    # A Pallas kernel reaches XLA as a custom call whose payload is the
    # serialized Mosaic module, MLIR locations included, and the cache key
    # hashes that payload. With full Python tracebacks in the locations
    # the key depends on the CALLER's stack, so `--replay` could never load
    # the program a node had compiled (seen on the v5e, PR 21: the Pallas
    # verify program missed the cache in the second process while every
    # XLA program hit). One frame per location makes the key a function of
    # the kernel alone.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    COMPILES.install()
    return cache_dir
