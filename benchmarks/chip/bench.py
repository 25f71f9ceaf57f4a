"""What every runner shares: clocks, spans, compile counting, the compile
cache, seeds, the device's description and the metric readers.

Nothing here names a cell, a configuration, a traffic mix or a metric:
``run.py`` finds those by the names in ``BENCHMARK.json``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import os
import pathlib
import sys
import time
from dataclasses import dataclass, field

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: JAX's persistent compilation cache: a fixed directory inside the
#: checkout, so that every run of a cell there finds what the first compiled
CACHE_DIR = ROOT / ".jax_cache"


def process_age_s() -> float:
    """Seconds since this process started (Linux: /proc, 10 ms steps)."""
    ticks = os.sysconf("SC_CLK_TCK")
    with open("/proc/self/stat") as f:
        # the command name may hold spaces: fields after its ')' are fixed
        start = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start / ticks


def process_start() -> float:
    """The process's start on the ``time.perf_counter`` clock."""
    return time.perf_counter() - process_age_s()


def enable_compile_cache() -> pathlib.Path:
    """Keep every compiled program, however fast it compiled, in the
    checkout's cache, so that only a cell's first run there compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CACHE_DIR


class CompileClock:
    """Counts XLA compilations as JAX reports them (copied from the
    bring-up script): each ``backend_compile_duration`` event is a program
    obtained, either compiled or loaded from the persistent cache, and each
    ``cache_hits`` event is one of the loads. Event times are kept, so a
    window can count what happened inside it."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.programs: list[float] = []     # perf_counter at each event
        self.hits: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.programs.append(time.perf_counter())

    def _event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.hits.append(time.perf_counter())

    def compiled_between(self, t0: float, t1: float) -> int:
        """Programs compiled, not loaded from the cache, in [t0, t1]."""
        got = sum(t0 <= t <= t1 for t in self.programs)
        return got - sum(t0 <= t <= t1 for t in self.hits)


@dataclass
class Spans:
    """Host spans around the benchmark's own calls into each layer.

    ``span(kind)`` records (start, end) on ``time.perf_counter`` and, while
    a profiler trace runs, a ``TraceAnnotation`` named ``bench.<kind>``, so
    that the trace can attribute device time and idle gaps to it."""
    traced: bool = False
    times: dict = field(default_factory=dict)

    @contextlib.contextmanager
    def span(self, kind: str):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{kind}")
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.times.setdefault(kind, []).append(
                    (t0, time.perf_counter()))


@dataclass
class Cell:
    """One run of one cell, as ``run.py`` hands it to the cell's runner."""
    name: str
    chips: int
    config: dict              # the configuration file, as it is run
    config_dir: pathlib.Path  # where its plain reference lies
    traffic: dict             # the traffic mix's parameters
    seed: int
    seconds: float
    trace: bool
    devices: list             # the chips this run may use
    started: float            # process start, on time.perf_counter
    trace_dir: str | None = None   # keep the raw trace here, if given


def load_module(path: pathlib.Path):
    """Import a file by its path (names in this benchmark may hold '-')."""
    name = "bench_" + path.stem.replace("-", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod         # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def prng_key(seed: int):
    """A JAX key from any whole seed, 64-bit ones included."""
    import jax
    key = jax.random.key(seed % 2 ** 32)
    return jax.random.fold_in(key, (seed >> 32) % 2 ** 31)


def percentile(values, q: float) -> float:
    """The q-th percentile (linear between order statistics)."""
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q))


def device_info(used) -> dict:
    """The device as JAX reports it: its platform, its kind, how many the
    process sees, and the peak memory on the fullest of the chips ``used``."""
    import jax
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": jax.device_count(), "memory_peak_bytes": peak}


def read_metrics(specs: list[dict], record: dict) -> dict:
    """Each metric's reader (``metrics/<name>.py``) applied to the run's
    record; a reader that finds nothing returns None and its metric is
    left out."""
    out = {}
    for spec in specs:
        reader = load_module(HERE / "metrics" / f"{spec['name']}.py")
        value = reader.read(record)
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out
