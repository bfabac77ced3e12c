"""XLA's work by program, from the events ``jax.monitoring`` already emits.

Between a process's chip claim and its first step lie the programs XLA
traces, lowers and compiles or loads from the persistent cache.  jax times
each itself and tells whoever listens (``jax._src.dispatch.log_elapsed_time``,
``jax._src.compiler.compile_or_get_cached``); this module listens and keeps
the job's totals, so that "was the run warm, which program recompiled, when,
and what did it cost" is answered from inside the program:

- counters, always on: ``xla.trace.us`` (jaxpr tracing), ``xla.lower.us``
  (jaxpr to MLIR), ``xla.backend.us`` (the backend's compile OR the load
  from the cache: what stalls the caller), ``xla.cache_load.us`` (the part
  of the latter that was retrieval), ``xla.cache.hits``,
  ``xla.cache.misses`` (entries WRITTEN: jax counts a miss where it stores
  the result) and ``xla.programs`` (every backend event);
- under ``TOS_TRACE=1`` a span per event in the ring, ``fun_name`` as tag,
  on the profiler's clock (jax stamps the events in epoch seconds, the
  tracer's anchor places them);
- one flight event ``xla_program`` (``fun_name``, ``start`` as epoch seconds,
  ``secs`` and its ``trace_secs`` / ``lower_secs`` / ``backend_secs`` /
  ``cache_load_secs``, ``cache`` ``"hit"`` or ``"miss"``) for every program
  whose trace + lower + backend time reaches :data:`PROGRAM_FLOOR_SECS`.
  Building a state from a seed dispatches dozens of one-op programs and
  the flight ring holds 256 events that deaths and restarts share: the small
  ones are in the counters alone.

A program's events arrive on the thread that compiles it, in order: its
trace, its lowering, then inside the backend interval the cache's hit (with
the retrieval time) or miss, then the backend event itself, which closes the
program.  A jitted function traced inside another's trace reports a time of
its own inside the outer one's (a step's trace holds thousands), and a
lowering rule may trace inside a lowering: only the part of an interval not
already counted is added, so ``xla.trace.us`` + ``xla.lower.us`` is wall
time on its thread.  A trace that no compile
follows (``jax.eval_shape``, a program found in memory) is counted, and
rides in the next program of its thread.

:func:`install` is called by a NODE, right after jax is first imported
there (``tpu_info.device_summary``, ``NodeContext.make_mesh``).  Never by a
driver — it must stay off jax — and never by importing jax in order to
listen: with jax absent from ``sys.modules`` it does nothing.
"""

from __future__ import annotations

import sys
import threading

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.telemetry import trace as ttrace

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

#: A program under this many seconds (trace + lower + backend) leaves no
#: flight event.
PROGRAM_FLOOR_SECS = 0.25
#: Counted intervals kept per thread to tell a nested one from a new one.  A
#: step's trace holds thousands of nested ones; past this many the oldest is
#: forgotten (and would count twice under a later enclosing interval).
_NESTING_WINDOW = 1 << 16

_SPAN_NAMES = {TRACE_EVENT: "xla.trace", LOWER_EVENT: "xla.lower",
               BACKEND_EVENT: "xla.backend"}

_install_lock = threading.Lock()
_installed = False
_local = threading.local()


class _Pending:
    """What one thread has seen since its last backend event."""

    __slots__ = ("counted", "start", "trace", "lower", "load", "hit")

    def __init__(self):
        self.counted: list[tuple[float, float]] = []
        self.reset()

    def reset(self) -> None:
        self.counted.clear()
        self.start: float | None = None
        self.trace = self.lower = self.load = 0.0
        self.hit = False


def _pending() -> _Pending:
    p = getattr(_local, "pending", None)
    if p is None:
        p = _local.pending = _Pending()
    return p


def _own_secs(p: _Pending, start: float, end: float) -> float:
    """Seconds of ``[start, end]`` that no earlier trace or lower event of
    this thread counted.  Events arrive at their END and intervals of one
    thread nest or follow one another, so ``counted`` is disjoint and in
    order, and what an enclosing interval holds is its tail."""
    inside = 0.0
    counted = p.counted
    while counted and counted[-1][0] >= start:
        s, e = counted.pop()
        inside += e - s
    counted.append((start, end))
    del counted[:-_NESTING_WINDOW]
    return max(0.0, (end - start) - inside)


def _add_us(name: str, secs: float) -> None:
    telemetry.counter(name).inc(int(secs * 1e6 + 0.5))


def _on_time_span(event: str, start: float, end: float, **kwargs) -> None:
    name = _SPAN_NAMES.get(event)
    if name is None:
        return
    fun_name = str(kwargs.get("fun_name", ""))
    secs = max(0.0, end - start)
    p = _pending()
    p.start = start if p.start is None else min(p.start, start)
    tags = {"fun_name": fun_name}
    if event == TRACE_EVENT:
        own = _own_secs(p, start, end)
        p.trace += own
        _add_us("xla.trace.us", own)
    elif event == LOWER_EVENT:
        own = _own_secs(p, start, end)
        p.lower += own
        _add_us("xla.lower.us", own)
    else:
        cache = "hit" if p.hit else "miss"
        tags["cache"] = cache
        _add_us("xla.backend.us", secs)
        telemetry.counter("xla.programs").inc()
        total = p.trace + p.lower + secs
        if total >= PROGRAM_FLOOR_SECS:
            ttrace.event("xla_program", fun_name=fun_name, start=p.start,
                         secs=total, trace_secs=p.trace, lower_secs=p.lower,
                         backend_secs=secs, cache_load_secs=p.load,
                         cache=cache)
        p.reset()
    ttrace.get_tracer().record_at(name, start, secs, tags)


def _on_duration(event: str, secs: float, **kwargs) -> None:
    if event == CACHE_LOAD_EVENT:
        _pending().load += secs
        _add_us("xla.cache_load.us", secs)


def _on_event(event: str, **kwargs) -> None:
    if event == CACHE_HIT_EVENT:
        _pending().hit = True
        telemetry.counter("xla.cache.hits").inc()
    elif event == CACHE_MISS_EVENT:
        telemetry.counter("xla.cache.misses").inc()


def install() -> bool:
    """Start listening, once a process; True when listening.  Nothing
    happens (False) under ``TOS_METRICS=0`` or while jax is not loaded:
    this never imports it."""
    global _installed
    if _installed:
        return True
    monitoring = getattr(sys.modules.get("jax"), "monitoring", None)
    if monitoring is None or not telemetry.enabled():
        return False
    with _install_lock:
        if not _installed:
            monitoring.register_event_time_span_listener(_on_time_span)
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
            _installed = True
    return True
