"""Sampled distributed tracing + flight recorder — the "where did it go" half
of the telemetry subsystem (stdlib-only).

The metrics registry (``registry.py``) answers "how much"; this module
answers "where did this request's 40 ms go" and "what happened in the 2 s
before that node died":

- **Spans** — structured records ``(trace_id, span_id, parent, monotonic
  start, duration, tags)`` written into **lock-free per-thread bounded
  rings**: each thread appends only to its own ring (list-slot assignment
  is atomic under the GIL, mirroring the registry's per-thread counter
  cells), so recording a span on the serving hot path costs an append and
  never takes a lock.  A full ring overwrites its oldest entries; the
  drain reports how many were lost.
- **Sampling** — ``TOS_TRACE`` (default off) gates everything; when on,
  ``TOS_TRACE_SAMPLE`` picks every ``round(1/rate)``-th root
  deterministically (a counter, not an RNG — identical runs sample
  identical requests, which is what the trace tests pin).  Child spans
  never re-sample: a context handed across threads/processes means the
  root already won the lottery.
- **Context propagation** — a :class:`TraceContext` is a plain
  ``(trace_id, span_id)`` pair, JSON- and pickle-safe, carried in wire
  frames (v3 ``infer_round``/``end_partition``) and queue markers so one
  request's spans assemble across processes.
- **Flight recorder** — every process keeps a separate bounded ring of
  structured *events* (deaths, restarts, retries, resyncs, reloads, fault
  injections; ``TOS_FLIGHT_EVENTS`` sizes it, 0 disables) independent of
  the trace switch, plus ``flight_snapshot()``/``dump_flight()`` so a
  chaos exit leaves a readable timeline behind.
- **Stages** — :func:`stage` marks a *layer boundary on a hot path*
  (per batch or per chunk, never per record).  One ``with`` block, three
  readers: the metrics registry (``<name>.us`` busy microseconds and
  ``<name>.calls``, always on), the ``jax.profiler`` timeline (a
  ``TraceAnnotation`` on the thread that did the work, when jax is loaded
  in this process) and, with ``TOS_TRACE=1``, this module's span ring
  (unsampled, nested under the enclosing stage of the thread).
- **Lifecycle** — :func:`lifecycle` is a stage that happens once a
  process (spawn, registration, the jax import, the chip claim, the
  map_fun, the drain, the driver's launch and shutdown): the stage's three
  readers plus one flight event ``lifecycle`` with its epoch start and its
  duration, so that order and gaps survive into the run report with
  tracing off (``report.build_lifecycle``).
- **Transport** — ``collect_delta()`` drains new spans/events for the
  heartbeat piggyback (``node.py``), stamped with this process's clock
  anchor and its current clock-offset estimate so the export can merge
  per-node streams onto one timeline (``trace_export.py``).

**Clocks.**  Durations and span starts are ``time.monotonic()`` seconds.
Every ``Tracer`` takes one *anchor* at creation — ``(time.monotonic(),
time.time_ns(), hostname)`` — and ships it with each stream, so the export
writes absolute microseconds since the Unix epoch.  That is the clock of a
``jax.profiler`` trace too (an xplane event starts at the ``Task
Environment`` plane's ``profile_start_time``, CLOCK_REALTIME nanoseconds,
plus its ``start_ns``), so ``trace.json`` lies beside the device timeline.
The heartbeat offset (driver-monotonic = local-monotonic + offset, the
NTP-style midpoint estimate from heartbeat RTTs) is used only to merge a
stream from *another host*, whose wall clock may be skewed; processes of
one host already share CLOCK_REALTIME.

Disabled (the default), every accessor returns ``None`` / a shared no-op
span, so instrumented code pays one attribute check.
"""

from __future__ import annotations

import itertools
import json
import os
import socket
import sys
import threading
import time
from typing import Any, NamedTuple

#: Per-thread span-ring capacity: recent-window postmortems need seconds of
#: history, the heartbeat drain empties it every ~2s — 2048 spans/thread
#: absorbs bursts well past both.
RING_SIZE = 2048
#: Max spans shipped per heartbeat delta (the rest ride the next one, or are
#: counted dropped by the ring overwrite if the producer outruns the drain).
DRAIN_SPAN_CAP = 1024
#: Flight-event ring default capacity (TOS_FLIGHT_EVENTS overrides; 0 off).
FLIGHT_EVENTS_DEFAULT = 256


class TraceContext(NamedTuple):
    """Wire-portable span identity: share ``trace_id``, parent ``span_id``.

    Serialized as a plain 2-tuple (pickle) / 2-list (JSON); ``coerce``
    accepts either back.
    """

    trace_id: int
    span_id: int

    @classmethod
    def coerce(cls, value) -> "TraceContext | None":
        if value is None:
            return None
        try:
            tid, sid = value
            return cls(int(tid), int(sid))
        except (TypeError, ValueError):
            return None


class _Ring:
    """Bounded append-only ring owned by ONE writer thread.

    ``buf[n % cap] = item; n += 1`` — the owning thread is the only writer,
    slot assignment is atomic under the GIL, and readers (the drain, the
    flight snapshot) tolerate racing a concurrent overwrite: they read
    whole immutable dicts, either the old span or the new one.
    """

    __slots__ = ("buf", "cap", "n", "owner")

    def __init__(self, cap: int):
        self.buf: list = [None] * cap
        self.cap = cap
        self.n = 0
        self.owner: threading.Thread | None = None  # writer, for dead-ring pruning

    def append(self, item) -> None:
        self.buf[self.n % self.cap] = item
        self.n += 1

    def read_from(self, cursor: int) -> tuple[list, int, int]:
        """(items, new_cursor, dropped) — entries appended since ``cursor``
        that are still in the ring."""
        n = self.n  # snapshot; concurrent appends land in the next drain
        start = max(cursor, n - self.cap)
        items = [self.buf[i % self.cap] for i in range(start, n)]
        return [x for x in items if x is not None], n, start - cursor

    def tail(self, limit: int) -> list:
        n = self.n
        start = max(0, n - min(self.cap, limit))
        return [x for x in (self.buf[i % self.cap] for i in range(start, n))
                if x is not None]


class _LiveSpan:
    """``with tracer.span(name, parent=ctx):`` — times the block and records
    it on exit; ``.ctx`` is the context to hand to children (including
    remote ones, before the span ends)."""

    __slots__ = ("_tracer", "name", "ctx", "_parent", "_tags", "_t0")

    def __init__(self, tracer: "Tracer", name: str, ctx: TraceContext,
                 parent: int | None, tags: dict | None):
        self._tracer = tracer
        self.name = name
        self.ctx = ctx
        self._parent = parent
        self._tags = tags

    def __enter__(self) -> "_LiveSpan":
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self._tracer.record_span(self.name, self.ctx, self._parent,
                                 self._t0, time.monotonic() - self._t0,
                                 self._tags)


class _NullSpan:
    """Shared no-op stand-in: disabled tracer / unsampled request."""

    __slots__ = ()
    ctx = None
    name = "<off>"

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def tick(self) -> None:     # a disabled stage (see _Stage.tick)
        return None


NULL_SPAN = _NullSpan()


class _Stage:
    """``with telemetry.stage(name):`` — see :func:`stage`."""

    __slots__ = ("_name", "_us", "_calls", "_tracer", "_annotation", "_t0",
                 "_mark", "_sid", "_parent", "_tags")

    def __init__(self, name: str, us, calls, tracer: "Tracer", annotation,
                 tags: dict | None = None):
        self._name = name
        self._us = us
        self._calls = calls
        self._tracer = tracer
        self._annotation = annotation
        self._tags = tags

    def __enter__(self) -> "_Stage":
        if self._annotation is not None:
            self._annotation.__enter__()
        tracer = self._tracer
        if tracer.enabled:
            local = tracer._local
            self._parent = getattr(local, "stage", None)
            self._sid = local.stage = tracer._new_id()
        else:
            self._sid = None    # decided here: exit records iff enter did
        self._t0 = self._mark = time.monotonic()
        return self

    def tick(self) -> None:
        """Add the time since enter (or the last tick) to ``<name>.us`` now,
        without ending the stage.  A stage that BLOCKS in slices (a bounded
        ``put`` retried every 100 ms) ticks once a slice, so that a reader
        of counter deltas over a window inherits at most one slice of a wait
        that began before its window — not the whole of it at exit."""
        now = time.monotonic()
        self._us.inc(int((now - self._mark) * 1e6 + 0.5))
        self._mark = now

    def __exit__(self, *exc) -> None:
        self.tick()
        self._calls.inc()
        if self._sid is not None:
            tracer = self._tracer
            tracer._local.stage = self._parent
            tracer.record_span(self._name,
                               TraceContext(tracer._loop_trace, self._sid),
                               self._parent, self._t0, self._mark - self._t0,
                               self._tags)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)


class _Lifecycle(_Stage):
    """``with telemetry.lifecycle(name):`` — see :func:`lifecycle`."""

    __slots__ = ("_wall",)

    def __enter__(self) -> "_Lifecycle":
        self._wall = time.time()
        return super().__enter__()

    def __exit__(self, *exc) -> None:
        super().__exit__(*exc)
        self._tracer.event("lifecycle", stage=self._name, start=self._wall,
                           secs=self._mark - self._t0, **(self._tags or {}))


class Tracer:
    """Process-local trace recorder (one per process, like the metrics
    registry).  All public methods are safe to call with tracing disabled —
    they return ``None``/no-ops and cost an attribute check."""

    def __init__(self, enabled: bool = False, sample: float = 0.01,
                 flight_events: int = FLIGHT_EVENTS_DEFAULT,
                 ring_size: int = RING_SIZE):
        self.enabled = bool(enabled)
        sample = min(1.0, float(sample))
        # deterministic counter sampling: every period-th root is traced
        self._period = max(1, round(1.0 / sample)) if sample > 0 else 0
        self._seq = itertools.count()        # CPython next() is atomic
        self._ids = itertools.count(1)
        # span ids carry per-process random high bits so two processes can
        # never mint the same id inside one merged trace; ids need no
        # determinism (sampling has it), so urandom is fine here
        self._id_base = int.from_bytes(os.urandom(6), "big") << 24
        self._ring_size = ring_size
        self._local = threading.local()
        self._rings_lock = threading.Lock()
        self._rings: list[_Ring] = []
        self._cursors: dict[int, int] = {}   # id(ring) -> drain cursor
        self.dropped = 0                     # spans lost to ring overwrite
        # drained-but-unshipped carryover (span-cap overflow, failed
        # heartbeat restore) — owned by the single drain thread, like
        # ``_cursors``; bounded so a dead coordinator can't grow it forever
        self._pending_spans: list = []
        self._pending_events: list = []
        # flight events: rare, multi-writer -> one small locked ring
        self._events_cap = max(0, int(flight_events))
        self._events = _Ring(self._events_cap) if self._events_cap else None
        self._events_lock = threading.Lock()
        self._events_cursor = 0
        #: driver-monotonic = local-monotonic + offset (heartbeat RTT
        #: midpoint estimate; None until the first heartbeat, 0.0 on the
        #: driver itself).  Last-write-wins float: atomic attribute store.
        self.clock_offset: float | None = None
        self.clock_rtt: float | None = None
        #: (monotonic seconds, epoch nanoseconds, host) read together once:
        #: what turns a span's monotonic start into the profiler's clock
        self.anchor = (time.monotonic(), time.time_ns(), socket.gethostname())
        #: the one trace every stage of this process belongs to ("the loop")
        self._loop_trace = self._new_id()

    # -- id allocation / sampling ---------------------------------------------

    def _new_id(self) -> int:
        # addition, not OR: injective for ANY counter value, so a process
        # that mints more than 2^24 ids (long fully-sampled soak) can never
        # alias an earlier id — OR would wrap into the base bits
        return self._id_base + next(self._ids)

    def sample(self) -> TraceContext | None:
        """Root sampling decision: a fresh root context for every
        ``round(1/TOS_TRACE_SAMPLE)``-th call, else None.  Deterministic —
        a counter, not an RNG."""
        if not self.enabled or not self._period:
            return None
        if next(self._seq) % self._period:
            return None
        return TraceContext(self._new_id(), self._new_id())

    def derive(self, parent: TraceContext | None) -> TraceContext | None:
        """A child context under ``parent`` (same trace, fresh span id) —
        for spans whose context must exist before they end."""
        if not self.enabled or parent is None:
            return None
        return TraceContext(parent[0], self._new_id())

    # -- recording ------------------------------------------------------------

    def _ring(self) -> _Ring:
        ring = getattr(self._local, "ring", None)
        if ring is None:
            ring = _Ring(self._ring_size)
            ring.owner = threading.current_thread()
            self._local.ring = ring
            with self._rings_lock:
                self._rings.append(ring)
        return ring

    def record_span(self, name: str, ctx: TraceContext | None,
                    parent: int | None, t0: float, dur: float,
                    tags: dict | None = None) -> None:
        """Append one finished span.  No-op when disabled or ``ctx`` is
        None (the unsampled path), so call sites need no guard."""
        if not self.enabled or ctx is None:
            return
        span = {"n": name, "t": ctx[0], "s": ctx[1], "p": parent,
                "t0": t0, "d": dur, "th": threading.get_ident()}
        if tags:
            span["tags"] = tags
        self._ring().append(span)

    def record_child(self, name: str, parent: TraceContext | None,
                     t0: float, dur: float,
                     tags: dict | None = None) -> TraceContext | None:
        """Record a retrospective child span under ``parent``; returns the
        child's context (None when unsampled/disabled)."""
        ctx = self.derive(parent)
        if ctx is not None:
            self.record_span(name, ctx, parent[1], t0, dur, tags)
        return ctx

    def span(self, name: str, parent: TraceContext | None = None,
             tags: dict | None = None, root: bool = False):
        """Context manager timing a live block.  ``parent=None`` records
        nothing unless ``root=True``, which applies root sampling."""
        if not self.enabled:
            return NULL_SPAN
        if parent is None:
            if not root:
                return NULL_SPAN
            ctx = self.sample()
            if ctx is None:
                return NULL_SPAN
            return _LiveSpan(self, name, ctx, None, tags)
        return _LiveSpan(self, name, self.derive(parent), parent[1], tags)

    def stage(self, name: str, registry, tags: dict | None = None,
              cls=_Stage):
        """See the module-level :func:`stage`; ``registry`` is the metrics
        registry whose ``<name>.us`` / ``<name>.calls`` counters it feeds."""
        if not registry.enabled:
            return NULL_SPAN
        annotate = _trace_annotation()
        return cls(name, registry.counter(name + ".us"),
                   registry.counter(name + ".calls"), self,
                   annotate(name) if annotate is not None else None, tags)

    def record_at(self, name: str, start: float, secs: float,
                  tags: dict | None = None) -> None:
        """A span of this process's loop trace whose start came as
        CLOCK_REALTIME seconds (the launcher's spawn stamp, a
        ``jax.monitoring`` time span): the anchor places it in the ring."""
        if self.enabled:
            self.record_span(name,
                             TraceContext(self._loop_trace, self._new_id()),
                             None,
                             self.anchor[0] + (start - self.anchor[1] / 1e9),
                             secs, tags)

    # -- flight recorder ------------------------------------------------------

    def event(self, kind: str, **fields) -> None:
        """Record one structured flight event (death/restart/retry/resync/
        reload/fault...).  Independent of the trace switch — gated only by
        ``TOS_FLIGHT_EVENTS`` (0 disables).  Rare by contract, so a small
        lock is fine."""
        if self._events is None:
            return
        ev = {"kind": kind, "t0": time.monotonic(), "wall": time.time()}
        if fields:
            ev.update(fields)
        with self._events_lock:
            self._events.append(ev)

    def flight_snapshot(self, span_limit: int = 512) -> dict:
        """Recent history for a postmortem dump: every flight event still in
        the ring plus the most recent spans of every thread, oldest first."""
        with self._events_lock:
            events = self._events.tail(self._events_cap) if self._events else []
        with self._rings_lock:
            rings = list(self._rings)
        spans: list = []
        for ring in rings:
            spans.extend(ring.tail(span_limit))
        spans.sort(key=lambda s: s["t0"])
        return {"events": list(events), "spans": spans,
                "clock_offset": self.clock_offset,
                "anchor": list(self.anchor)}

    # -- transport (heartbeat piggyback) --------------------------------------

    def collect_delta(self, span_cap: int = DRAIN_SPAN_CAP) -> dict | None:
        """New spans/events since the last collect, for the heartbeat
        piggyback; None when there is nothing to ship.  Spans only travel
        while tracing is on; flight events travel whenever their ring is
        enabled.  Single-consumer: the heartbeat thread (it owns the drain
        cursors and the pending carryover)."""
        payload: dict = {}
        if self.enabled:
            with self._rings_lock:
                rings = list(self._rings)
            spans, self._pending_spans = self._pending_spans, []
            dead: list[_Ring] = []
            for ring in rings:
                got, cursor, lost = ring.read_from(
                    self._cursors.get(id(ring), 0))
                self._cursors[id(ring)] = cursor
                self.dropped += lost
                spans.extend(got)
                # a dead writer appends nothing more: once its ring is fully
                # drained, drop it (a long soak with elastic restarts mints a
                # 2048-slot ring per short-lived recording thread otherwise)
                if (ring.owner is not None and not ring.owner.is_alive()
                        and cursor >= ring.n):
                    dead.append(ring)
            if dead:
                with self._rings_lock:
                    for ring in dead:
                        self._rings.remove(ring)
                        self._cursors.pop(id(ring), None)
            if spans:
                spans.sort(key=lambda s: s["t0"])
                if len(spans) > span_cap:
                    # overflow rides the next beat (bounded: past 4 beats'
                    # worth the oldest are dropped and counted)
                    carry = spans[:-span_cap]
                    spans = spans[-span_cap:]
                    excess = len(carry) - 4 * span_cap
                    if excess > 0:
                        self.dropped += excess
                        carry = carry[excess:]
                    self._pending_spans = carry
                payload["spans"] = spans
        if self._events is not None:
            events, self._pending_events = self._pending_events, []
            with self._events_lock:
                got_ev, self._events_cursor, _ = self._events.read_from(
                    self._events_cursor)
            events.extend(got_ev)
            if events:
                payload["events"] = events
        if not payload:
            return None
        payload["anchor"] = list(self.anchor)
        if self.clock_offset is not None:
            payload["offset"] = self.clock_offset
            payload["rtt"] = self.clock_rtt
        if self.dropped:
            payload["dropped"] = self.dropped
        return payload

    def collect_final(self) -> dict | None:
        """Everything still unshipped, uncapped — the one-shot drain for
        paths with no next beat (deregister's final delta, the driver's
        export gather): the span-cap defer contract must not strand the
        carryover when this is the last collect."""
        return self.collect_delta(span_cap=1 << 62)

    def restore_delta(self, payload: dict | None) -> None:
        """Give a failed heartbeat's drained delta back so the next beat
        re-ships it: unlike metric deltas (absolute values, implicitly
        re-sent), drained spans and flight events are not re-derivable.
        Same single-consumer contract as ``collect_delta``."""
        if not payload:
            return
        spans = payload.get("spans")
        if spans:
            self._pending_spans = list(spans) + self._pending_spans
        events = payload.get("events")
        if events:
            self._pending_events = list(events) + self._pending_events

    def note_clock(self, offset: float, rtt: float) -> None:
        """Adopt a heartbeat's clock estimate when it beats (or refreshes)
        the current one: the lowest-RTT midpoint is the least skewed, but a
        stale low-RTT estimate must not pin forever against drift — a new
        reading within 2x the best RTT refreshes it, and every rejected
        reading relaxes the bar a little so a permanently degraded network
        (best-ever RTT no longer achievable) re-arms within ~15 beats
        instead of freezing the offset for the rest of the run."""
        best = self.clock_rtt
        if best is None or rtt <= 2.0 * best:
            self.clock_offset = float(offset)
            self.clock_rtt = float(rtt) if best is None else min(best, rtt)
        else:
            self.clock_rtt = best * 1.05


# -- process-local singleton ---------------------------------------------------

_lock = threading.Lock()
_tracer: Tracer | None = None


def get_tracer() -> Tracer:
    """The process tracer, created on first use from the TOS_TRACE knobs."""
    global _tracer
    t = _tracer
    if t is None:
        with _lock:
            if _tracer is None:
                from tensorflowonspark_tpu.utils.envtune import (
                    env_bool,
                    env_float,
                    env_int,
                )

                _tracer = Tracer(
                    enabled=env_bool("TOS_TRACE", False),
                    sample=env_float("TOS_TRACE_SAMPLE", 0.01),
                    flight_events=env_int("TOS_FLIGHT_EVENTS",
                                          FLIGHT_EVENTS_DEFAULT, minimum=0))
            t = _tracer
    return t


def reset(enabled: bool | None = None, sample: float | None = None,
          flight_events: int | None = None) -> Tracer:
    """Replace the process tracer (tests / the bench's off-vs-on compare):
    re-reads the env knobs unless overridden."""
    global _tracer
    with _lock:
        from tensorflowonspark_tpu.utils.envtune import (
            env_bool,
            env_float,
            env_int,
        )

        _tracer = Tracer(
            enabled=(env_bool("TOS_TRACE", False) if enabled is None
                     else enabled),
            sample=(env_float("TOS_TRACE_SAMPLE", 0.01) if sample is None
                    else sample),
            flight_events=(env_int("TOS_FLIGHT_EVENTS",
                                   FLIGHT_EVENTS_DEFAULT, minimum=0)
                           if flight_events is None else flight_events))
        return _tracer


def enabled() -> bool:
    return get_tracer().enabled


def sample() -> TraceContext | None:
    return get_tracer().sample()


def derive(parent: TraceContext | None) -> TraceContext | None:
    return get_tracer().derive(parent)


def span(name: str, parent: TraceContext | None = None,
         tags: dict | None = None, root: bool = False):
    return get_tracer().span(name, parent, tags, root=root)


_annotation_cls = None   # jax.profiler.TraceAnnotation, once jax is loaded


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` if jax is ALREADY loaded in this
    process, else None.  Never imports jax (telemetry stays stdlib-only, and
    a driver that must stay off the chip stays off it); the class is kept
    once found, so the steady state is one global read."""
    global _annotation_cls
    if _annotation_cls is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        _annotation_cls = getattr(profiler, "TraceAnnotation", None)
    return _annotation_cls


def stage(name: str):
    """Context manager for a *layer boundary on a hot path* — enter it per
    batch or per chunk, NEVER per record.  On exit it

    - adds the elapsed microseconds to the counter ``<name>.us`` and 1 to
      ``<name>.calls`` (lock-free per-thread cells: busy time and count are
      recorded where the work happens, with no lock, reservoir or sampling).
      Under ``TOS_METRICS=0`` the whole stage is the shared no-op;
    - has been a ``jax.profiler.TraceAnnotation(name)`` for the duration, if
      jax is loaded in this process: with a profiler trace running and its
      host tracer on, the stage is on the profiler's own timeline, on the
      thread that did the work — the clock of the device ops;
    - with ``TOS_TRACE=1``, is also recorded in this thread's span ring,
      unsampled, its parent the enclosing stage of the thread and its trace
      id one per process, so ``<log_dir>/trace.json`` shows the loop.

    A stage that blocks in slices calls ``.tick()`` on the object the
    ``with`` binds, once a slice (see :meth:`_Stage.tick`)."""
    from tensorflowonspark_tpu import telemetry

    return get_tracer().stage(name, telemetry.get_registry())


def lifecycle(name: str, **tags):
    """A :func:`stage` that happens ONCE A PROCESS, where a job's time goes
    when no step runs: launch, spawn, registration, the jax import, the chip
    claim, the map_fun, the drain, the driver's shutdown.  Besides the
    stage's three readers it leaves one flight event ``lifecycle`` with
    ``stage``, ``start`` (epoch seconds) and ``secs`` (and the ``tags``), so
    the order of the stages and the gaps between them reach the run report's
    ``lifecycle`` block with tracing off.  Never on a per-step path: the
    flight ring holds 256 events."""
    from tensorflowonspark_tpu import telemetry

    return get_tracer().stage(name, telemetry.get_registry(), tags or None,
                              cls=_Lifecycle)


def record_lifecycle(name: str, start: float, secs: float, **tags) -> None:
    """:func:`lifecycle` for a stage that began before this process could
    time it (``node.spawn`` starts in the launcher): ``start`` is epoch
    seconds on a clock this process shares with whoever stamped it."""
    from tensorflowonspark_tpu import telemetry

    registry = telemetry.get_registry()
    if not registry.enabled:
        return
    registry.counter(name + ".us").inc(int(secs * 1e6 + 0.5))
    registry.counter(name + ".calls").inc()
    tracer = get_tracer()
    tracer.record_at(name, start, secs, tags or None)
    tracer.event("lifecycle", stage=name, start=start, secs=secs, **tags)


def record_span(name: str, ctx: TraceContext | None, parent: int | None,
                t0: float, dur: float, tags: dict | None = None) -> None:
    get_tracer().record_span(name, ctx, parent, t0, dur, tags)


def record_child(name: str, parent: TraceContext | None, t0: float,
                 dur: float, tags: dict | None = None) -> TraceContext | None:
    return get_tracer().record_child(name, parent, t0, dur, tags)


def event(kind: str, **fields) -> None:
    get_tracer().event(kind, **fields)


def collect_delta() -> dict | None:
    return get_tracer().collect_delta()


def collect_final() -> dict | None:
    return get_tracer().collect_final()


def flight_snapshot(span_limit: int = 512) -> dict:
    return get_tracer().flight_snapshot(span_limit)


def dump_flight(path: str, node: str = "") -> str:
    """Write this process's flight snapshot as JSON (the chaos-exit
    postmortem; ``faultinject`` calls this in the instant before a
    self-SIGKILL).  Returns ``path``."""
    snap = flight_snapshot()
    snap["schema"] = "tos-flight-v1"
    snap["node"] = node
    snap["pid"] = os.getpid()
    with open(path, "w", encoding="utf-8") as f:
        json.dump(snap, f)
        f.write("\n")
    return path


def map_time(t0: float, offset: float | None) -> float:
    """Local monotonic -> driver-monotonic (identity when no estimate)."""
    return t0 + (offset or 0.0)


def event_origin(key: str) -> str:
    """The recording process behind a stream key: a chaos dump
    (``flight:node0``) and the heartbeat-shipped stream (``node0``) share
    one origin, so their common events can be deduplicated."""
    return key[len("flight:"):] if key.startswith("flight:") else key


def merge_events(streams: dict[str, dict]) -> list[dict]:
    """Flatten per-stream flight events onto the driver timeline: each
    event gains ``node`` and ``t`` (driver-monotonic seconds), ordered by
    ``t``.  ``streams`` maps a node key to ``{"events": [...],
    "offset": float|None}`` (the trace-stream / flight-dump shape).

    A chaos dump repeats events its process already shipped on heartbeats
    (the drain advances a cursor, the dump tails the whole ring), so events
    identical per origin are emitted once — heartbeat copy preferred (its
    stream carries them with the offset they shipped under)."""
    out: list[dict] = []
    seen: set = set()
    for key in sorted(streams, key=lambda k: (k.startswith("flight:"), k)):
        stream = streams[key]
        offset = stream.get("clock_offset", stream.get("offset"))
        for ev in stream.get("events") or ():
            ident = (event_origin(key), ev.get("kind"), ev.get("t0"),
                     ev.get("wall"))
            if ident in seen:
                continue
            seen.add(ident)
            ev = dict(ev)
            ev["node"] = key
            ev["t"] = map_time(float(ev.get("t0", 0.0)), offset)
            out.append(ev)
    out.sort(key=lambda e: e["t"])
    return out


def coerce_context(value: Any) -> TraceContext | None:
    """Best-effort TraceContext from a wire value (tuple/list/None)."""
    return TraceContext.coerce(value)
