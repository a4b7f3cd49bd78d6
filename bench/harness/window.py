"""The measured window of one run: the set-up clock, the window's own
clock, the driver's spans, the profiler (with `--trace 1`), the count of
compiles inside the window and the device memory peak.

With `--trace 1` the profiler records the first `trace_seconds` of the
window (the traffic file's `trace_seconds`): the driver calls `tick(n)`
after each unit of work (a fit), which counts the `n` generations done
while tracing and stops the profiler at the first unit boundary past
that length; a trace of a whole window would hold millions of device
operations. Stopping the profiler writes the trace out, which takes
seconds; the window's clock leaves that pause out, so that the window's
length is as in an untraced run."""
from __future__ import annotations

import contextlib
import glob
import tempfile
import time


class Window:
    def __init__(self, t_start: float, trace: bool, devices,
                 trace_seconds: float = 5.0):
        self.t_start = t_start
        self.trace = trace
        self.trace_seconds = trace_seconds
        self.traced_units = 0
        self.paused = 0.0  # seconds the profiler's stop took
        self.read_s = 0.0  # seconds reading the trace took
        self._traced = None  # the open bench.traced annotation
        self.devices = devices
        self.setup_s = None
        self.elapsed = None
        self.compiles = 0
        self.memory_peak_bytes = None
        self.reduced = None  # trace_reduce.Reduced of the traced window
        self._counting = False
        self._tmp = None
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, duration: float, **_):
        if self._counting and event.endswith("backend_compile_duration"):
            self.compiles += 1

    def setup_done(self):
        """Set-up ends here: data made, programs compiled or loaded, and
        the warm-up's results waited for."""
        self.setup_s = time.perf_counter() - self.t_start

    def span(self, name: str):
        """A driver span; recorded into the profiler's trace when tracing."""
        if self.trace:
            import jax

            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    @contextlib.contextmanager
    def measure(self):
        """The window: yields a clock (seconds since the window opened)."""
        import jax

        if self.setup_s is None:
            self.setup_done()
        if self.trace:
            self._tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._tmp.name, profiler_options=opts)
            self._traced = jax.profiler.TraceAnnotation("bench.traced")
            self._traced.__enter__()
        self._counting = True
        self._t0 = time.perf_counter()
        try:
            yield self.clock
            self.elapsed = self.clock()
        finally:
            self._counting = False
            self._stop_trace()

    def clock(self) -> float:
        """Seconds since the window opened, less the profiler's stop."""
        return time.perf_counter() - self._t0 - self.paused

    def tick(self, units: int = 1):
        """One unit of work is done; stop tracing once long enough."""
        if self._traced is None:
            return
        self.traced_units += units
        if self.clock() >= self.trace_seconds:
            self._stop_trace()

    def _stop_trace(self):
        if self._traced is not None:
            import jax

            self._traced.__exit__(None, None, None)
            self._traced = None
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.paused += time.perf_counter() - t

    def read_memory(self):
        """Peak device memory of the fullest chip used (after the window,
        before the reference runs)."""
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        self.memory_peak_bytes = max(peaks) if peaks else None

    def reduce_trace(self):
        """Reduce the traced window (`--trace 1`), then delete the trace."""
        import trace_reduce

        if not self.trace or self._tmp is None:
            return None
        try:
            files = glob.glob(f"{self._tmp.name}/**/*.xplane.pb", recursive=True)
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            t = time.perf_counter()
            ops, spans = trace_reduce.read_xplane(files[0])
            used = [f"/device:TPU:{d.id}" for d in self.devices]
            self.reduced = trace_reduce.reduce(ops, spans, devices=used)
            self.read_s = time.perf_counter() - t
        finally:
            self._tmp.cleanup()
            self._tmp = None
        return self.reduced
