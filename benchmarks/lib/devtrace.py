"""Profiling the process that holds the chip, and what JAX says of it."""

from __future__ import annotations

import shutil
import tempfile
import time


class CompileCount:
    """Backend compilations JAX reports (jax.monitoring). Inside a
    measured window there should be none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _seconds: float, **_kw) -> None:
        if event == self.EVENT:
            self.n += 1


def device_facts() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """The peak on the fullest chip, as the backend reports it (0 where
    it reports none, as XLA:CPU)."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Tracer:
    """One traced window: ``start()`` ... ``stop()`` -> reduced trace.
    The trace goes to a directory of its own under TMPDIR, removed once
    it is read."""

    def __init__(self, emulated: bool):
        self.dir = None
        self.emulated = emulated
        self._span = None
        self.t0 = 0.0
        self.seconds = 0.0

    def start(self) -> None:
        import jax
        from jax.profiler import ProfileOptions

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = ProfileOptions()
        opts.python_tracer_level = 0     # the Python tracer slows the host
        opts.host_tracer_level = 2       # TraceAnnotations and jax's own
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.t0 = time.perf_counter()

    def stop(self, keep_plain: bool = False):
        """-> tracered.reduce_trace's result (None without a trace).
        ``keep_plain`` leaves the plain form in ``self.plain`` (how the
        recorded trace under tests/data was made)."""
        import jax

        from benchmarks.lib import tracered

        self.seconds = time.perf_counter() - self.t0
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        path = tracered.find_xplane(self.dir)
        try:
            if path is None:
                return None
            plain = tracered.load_xplane(path)
            if keep_plain:
                self.plain = plain
            return tracered.reduce_trace(plain, emulated=self.emulated)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
