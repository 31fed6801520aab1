import dataclasses
import glob
import os
import sys
import time

import pytest

# Tests must see 1 CPU device (the dry-run sets its own 512-device flag in
# its OWN process via subprocess); never set XLA_FLAGS globally here.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@dataclasses.dataclass
class Span:
    name: str
    start: float                     # profiler ns
    end: float
    stats: dict
    line: tuple                      # (plane, line): one host thread


class ProgramTrace:
    """A CPU profiler trace around a ``with`` block; afterwards ``spans``
    holds the program's ``tidal.*`` host spans.  The persistent compile
    cache is off inside, so every compile the block causes is counted."""

    MARK = "test.clock_mark"

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.spans: list = []
        self._mark_s = self._mark_ns = None
        self._cache_was = None

    def __enter__(self):
        import jax
        from jax.experimental.compilation_cache import compilation_cache
        self._cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._mark_s = time.perf_counter()
        with jax.profiler.TraceAnnotation(self.MARK):
            pass
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        jax.config.update("jax_enable_compilation_cache", self._cache_was)
        path = max(glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
        pd = jax.profiler.ProfileData.from_file(path)
        for i, plane in enumerate(pd.planes):
            if not plane.name.startswith("/host:"):
                continue
            for j, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name == self.MARK:
                        self._mark_ns = e.start_ns
                    elif e.name.startswith("tidal."):
                        self.spans.append(Span(
                            e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats), (i, j)))
        return False

    def ns(self, perf_counter_s: float) -> float:
        """A ``time.perf_counter`` stamp on the trace's clock."""
        return self._mark_ns + (perf_counter_s - self._mark_s) * 1e9

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]


@pytest.fixture
def profiled(tmp_path):
    """Trace a block of the test on the CPU profiler (see ProgramTrace)."""
    return ProgramTrace(str(tmp_path / "trace"))
