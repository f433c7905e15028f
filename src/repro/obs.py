"""The program's observability: layer scope names, host spans, compile counts.

There is one tracing system, JAX's profiler.

* **Layer scopes.** The models put each layer's work under a
  ``jax.named_scope`` named here (``attn``, ``moe`` with its children,
  ``head``). The scope reaches the compiled program as every HLO
  instruction's ``op_name`` metadata, and from there the profiler trace,
  which carries each program's HLO. Work outside every scope (pre-norms,
  embedding, residual adds, the layer scan's weight slicing) is "other".
* **Host spans.** :func:`span` is a ``jax.profiler.TraceAnnotation`` named
  ``repro.<name>``, on the same clock as the device planes, that also adds
  its count and seconds to an in-memory total per name (:func:`spans`).
* **Compiles.** Every backend compile in the process, or load from the
  persistent cache, is counted per program (``jit(<function>)``), with its
  seconds, from JAX's own monitoring event (:func:`compiles`).

Only totals are kept, never a record per event.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from collections import defaultdict

import jax

__all__ = [
    "ATTN", "MOE", "HEAD", "ROUTER", "DISPATCH", "A2A", "EXPERTS", "COMBINE",
    "scoped", "Total", "span", "spans", "compiles",
]

# Layer scopes.
ATTN = "attn"
MOE = "moe"
HEAD = "head"
# Scopes inside ``moe``.
ROUTER = "router"
DISPATCH = "dispatch"
A2A = "a2a"
EXPERTS = "experts"
COMBINE = "combine"

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def scoped(name: str):
    """Decorator: run the function under ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


@dataclasses.dataclass
class Total:
    count: int = 0
    seconds: float = 0.0

    def add(self, seconds: float) -> None:
        self.count += 1
        self.seconds += seconds


_spans: dict[str, Total] = defaultdict(Total)
_compiles: dict[str, Total] = defaultdict(Total)


@contextlib.contextmanager
def span(name: str):
    """Host span ``repro.<name>`` in the profiler trace, totalled in memory.
    Yields a ``Total`` that holds this span's own seconds once it ends."""
    this = Total()
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("repro." + name):
            yield this
    finally:
        this.add(time.perf_counter() - t0)
        _spans[name].add(this.seconds)


def spans() -> dict[str, Total]:
    """Count and seconds of every span so far, by name."""
    return {k: dataclasses.replace(v) for k, v in _spans.items()}


def compiles() -> dict[str, Total]:
    """Count and seconds of the backend compiles (or cache loads) so far,
    by program."""
    return {k: dataclasses.replace(v) for k, v in _compiles.items()}


def _on_event(event: str, secs: float, **kw) -> None:
    if event == COMPILE_EVENT:
        _compiles[str(kw.get("fun_name", "?"))].add(secs)


# Registered once, when the module is first imported.
jax.monitoring.register_event_duration_secs_listener(_on_event)
