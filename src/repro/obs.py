"""The program's own spans and compile counter.

``span(name)`` marks a phase of the program (``with span("engine.step"):``).
Spans are off by default, and then a span is one shared no-op context
returned after a single check of a module flag: no string is built and JAX
is not touched, so a hot loop may hold several. ``enable(True)`` turns
them on for the process: each span then records its ``(start, end)`` on
``time.perf_counter`` under its name (read with ``spans()``) and opens a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so that a running
profiler trace holds it on its host plane, on the device trace's clock.

``compile_counter()`` is the process's one ``CompileCounter``, registered
with ``jax.monitoring`` on its first call: which jitted functions were
compiled or loaded from the persistent cache, when, and how long it took.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass

_on = False
_spans: dict[str, list[tuple[float, float]]] = {}
_OFF = contextlib.nullcontext()


def enable(on: bool) -> None:
    """Turn spans on or off for the process. Turning them on starts a new
    record."""
    global _on
    if on:
        _spans.clear()
    _on = bool(on)


def spans() -> dict[str, list[tuple[float, float]]]:
    """Name -> the ``(start, end)`` of each span recorded since spans were
    last turned on, on ``time.perf_counter``. The record grows by one
    pair per span: turn spans on for a bounded run, not for a server's
    life."""
    return _spans


def span(name: str):
    """A context that marks one phase of the program (see the module)."""
    if not _on:
        return _OFF
    return _Span(name)


class _Span:
    __slots__ = ("name", "ann", "t0")

    def __init__(self, name: str):
        import jax
        self.name = name
        self.ann = jax.profiler.TraceAnnotation(f"repro.{name}")

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        _spans.setdefault(self.name, []).append((self.t0,
                                                 time.perf_counter()))
        return self.ann.__exit__(*exc)


@dataclass(frozen=True)
class Compile:
    """One program obtained by XLA for a jitted function."""
    fun_name: str        # as JAX names it: "jit(engine_decode)"
    seconds: float       # compiling it, or loading it from the cache
    cached: bool         # loaded from the persistent compilation cache
    end: float           # when it was obtained, on time.perf_counter


class CompileCounter:
    """Every program JAX obtains from XLA, by the jitted function's name.

    JAX reports each as a ``backend_compile_duration`` event carrying
    ``fun_name``, whether compiled or loaded from the persistent cache; a
    load also reports a ``cache_hits`` event first, inside the same
    compile, so the next duration event is marked as cached."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.events: list[Compile] = []
        self.cache_hits = 0
        self._hit = False

    def _duration(self, event: str, secs: float, fun_name: str = "?",
                  **_) -> None:
        if event == self.EVENT:
            self.events.append(Compile(fun_name, secs, self._hit,
                                       time.perf_counter()))
            self._hit = False

    def _event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.cache_hits += 1
            self._hit = True

    @property
    def programs(self) -> int:
        return len(self.events)

    @property
    def seconds(self) -> float:
        return sum(e.seconds for e in self.events)


_counter: CompileCounter | None = None


def compile_counter() -> CompileCounter:
    """The process's compile counter; it counts from its first call."""
    global _counter
    if _counter is None:
        import jax
        _counter = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(
            _counter._duration)
        jax.monitoring.register_event_listener(_counter._event)
    return _counter
