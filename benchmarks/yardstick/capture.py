"""What ``--trace 1`` adds to a run: a ``jax.profiler`` capture of the
measured window from inside the process that holds the chip,
``TraceAnnotation`` around the harness's own calls, and the program's
spans. With ``--trace 0`` every method here is a no-op, so the
deployment runs as its file states it."""

from __future__ import annotations

import contextlib
import os
import shutil
import time

# The annotation a driver holds open for exactly its measured window: a
# capture may run on behind the window (the check of the device path),
# and what a per-layer metric reads from the trace is cut to this.
WINDOW = "measured.window"


class Capture:
    """The driver calls ``start()`` just before its measured window,
    holds ``annotate(WINDOW)`` open for the window itself, and calls
    ``finish()`` behind it (writing a trace out takes seconds, which
    must not fall inside a window)."""

    def __init__(self, enabled: bool, out_dir: str):
        self.enabled = enabled
        self.out_dir = out_dir
        self.state = "idle" if enabled else "off"
        self.t_start = self.t_stop = None
        self.spans: list = []
        self._null = contextlib.nullcontext()

    def annotate(self, name: str):
        """A host span in the profiler's own trace, on the device's clock."""
        if self.state != "tracing":
            return self._null
        import jax.profiler

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        if self.state != "idle":
            return
        import jax.profiler

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the interpreter's own frames: no
        opts.host_tracer_level = 1  # TraceAnnotation, not XLA's internals
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.state = "tracing"
        self.t_start = time.perf_counter()

    def finish(self) -> None:
        """Stop the capture and write the trace out."""
        if self.state != "tracing":
            return
        import jax.profiler

        self.t_stop = time.perf_counter()
        self.state = "stopping"
        jax.profiler.stop_trace()
        self.state = "done"

    def trace_file(self):
        """-> the path of the ``.xplane.pb`` the capture wrote, or None."""
        if self.state != "done":
            return None
        for root, _dirs, files in os.walk(self.out_dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(root, f)
        return None

    def collect_spans(self, tracer) -> None:
        """Drain the program's span ring into this capture (whole window:
        the ring holds 16,384 events and would wrap)."""
        if self.enabled and tracer is not None:
            self.spans.extend(tracer.chrome_trace(reset=True)["traceEvents"])
