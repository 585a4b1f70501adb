#!/usr/bin/env python3
"""One run of one cell: ``python benchmarks/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``.

Everything that belongs to a cell is data found by name: the cell in
``BENCHMARK.json``, its configuration under ``configs/``, its traffic
under ``traffic/``, the traffic's driver under ``drivers/`` and each
per-layer metric's reader under ``layers/``. This file holds no cell,
configuration or metric name (``setup_s`` is the contract's).

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``); progress goes to standard error. Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result. ``--rehearsal`` is the CPU rehearsal of a cell's files at toy
sizes (the files under ``rehearsal/`` laid over the cell's own): it
accepts any platform and withholds every timing metric. ``--manifest``
names another manifest than the repo's, for a cell that is not in it
yet (and for the sweep that found a traffic file's fixed rate).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

T_PROCESS = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")
REQUIRED_PLATFORM = "tpu"
SETUP_METRIC = "setup_s"
TIMED_SOURCES = ("host_clock", "device_trace", "program_span")


def say(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


class Context:
    """What a driver is handed."""

    def __init__(self, args, files, cell_dir):
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearsal = bool(args.rehearsal)
        self.config = files["config"]
        self.ini_template = files["ini"]
        self.traffic = files["traffic"]
        self.cache_dir = CACHE
        self.work_root = os.path.join(cell_dir, "work")
        self.trace_dir = os.path.join(cell_dir, "trace")
        self.say = say
        self.cap = None

    def capture(self):
        """The run's profiler capture (a no-op object with --trace 0)."""
        from yardstick.capture import Capture

        self.cap = Capture(self.trace, self.trace_dir)
        return self.cap


def load_driver(path: str):
    spec = importlib.util.spec_from_file_location("cell_driver", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def device_report(jax, chips: int) -> dict:
    devices = jax.devices()
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": chips,
        "memory_peak_bytes": peak,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(REPO, "BENCHMARK.json"))
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, REPO)
    sys.path.insert(0, BENCH)
    from yardstick import manifest, readers

    m = manifest.load(args.manifest)
    manifest.validate(m, REPO)
    files = manifest.cell_files(m, args.workload, REPO, args.rehearsal)
    if args.seconds is None:
        args.seconds = m["run_seconds"]
    chips = files["cell"]["chips"]

    if not args.rehearsal:
        # the compile cache at a fixed path inside the checkout: the path
        # is part of the cache's key, and the program takes the directory
        # this variable names
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    from stellard_tpu.crypto.backend import ensure_jax

    jax = ensure_jax()
    devices = jax.devices()
    if not args.rehearsal and (
        devices[0].platform != REQUIRED_PLATFORM or len(devices) < chips
    ):
        say(f"JAX found {len(devices)} device(s) of platform "
            f"{devices[0].platform!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}); the cell needs "
            f"{chips} of {REQUIRED_PLATFORM!r}: no result")
        return 3
    if not args.rehearsal:
        from yardstick import peaks

        peaks.peaks_for(devices[0].device_kind)

    cell_dir = os.path.join(CACHE, "cells", args.workload)
    shutil.rmtree(cell_dir, ignore_errors=True)
    ctx = Context(args, files, cell_dir)
    driver = load_driver(files["driver_path"])
    try:
        result = driver.run(ctx)
        sources = result["sources"]
        device = device_report(jax, chips)
        breakdown = None
        if ctx.trace:
            from yardstick import capture, xtrace

            cap = ctx.cap
            if cap is not None and cap.trace_file():
                reduced = xtrace.reduce_file(
                    cap.trace_file(), set(result.get("annotations", ())),
                    chips, capture.WINDOW)
                if reduced is not None:
                    # the contract's two numbers are the whole capture's
                    # (the window and, in a node cell, the device-path
                    # check behind it); the per-layer metrics read the
                    # window alone
                    sources["trace"] = reduced
                    device["busy_s"] = reduced["busy_s"]
                    device["window_s"] = cap.t_stop - cap.t_start
                    breakdown = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
                    say(f"capture {device['window_s']:.3f}s, device busy "
                        f"{reduced['busy_s']:.6f}s; measured window "
                        f"{reduced['window_s']:.3f}s on the trace's clock"
                        f"{'' if reduced['window_marked'] else ' (NOT marked)'}"
                        f", device busy {reduced['window_busy_s']:.6f}s")
                    say("programs started inside the window: " + json.dumps(
                        {k: [round(v[0], 6), v[1]]
                         for k, v in sorted(reduced["programs"].items())}))
    finally:
        shutil.rmtree(cell_dir, ignore_errors=True)  # store, trace files

    say(f"{SETUP_METRIC} {result['t_first_measured'] - T_PROCESS:.3f}")
    if not args.rehearsal:
        say("end to end: " + json.dumps(result["end_to_end"]))
    for line in result.get("problems", []):
        say(f"NOT CORRECT: {line}")
    compiled = (sources.get("counters") or {}).get("xla.programs")
    say(f"programs compiled inside the window: {json.dumps(compiled)}")

    group = "per_layer" if ctx.trace else "end_to_end"
    metrics = {}
    for x in manifest.metrics_of(m, args.workload, group):
        if group == "end_to_end":
            if x["name"] == SETUP_METRIC:
                value = result["t_first_measured"] - T_PROCESS
            else:
                value = result["end_to_end"].get(x["name"])
        else:
            value = readers.read_metric(
                manifest.reader_file(BENCH, x["name"]), sources)
        if value is None:
            say(f"{x['name']}: nothing to read, left out")
            continue
        if args.rehearsal and x["source"] in TIMED_SOURCES:
            continue  # a CPU run gives no time, rate or share
        metrics[x["name"]] = {"value": value, "unit": x["unit"]}

    line = {
        "correct": bool(result["correct"]),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None and not args.rehearsal:
        line["breakdown"] = breakdown
    if args.rehearsal:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
