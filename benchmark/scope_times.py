"""Device time by ``jax.named_scope``, from a traced run's xplane.

The program names where its device time goes (``loss_and_grad``,
``optimizer_update``, ``attention``, ``moe/experts`` ...).  XLA carries the
scope path of the instruction a device op came from in the op's ``op_name``
metadata, and the profiler writes it into the trace as the stat ``tf_op`` of
the event's METADATA (``XEventMetadata.stats``), not of the event:
``jax.profiler.ProfileData`` shows an event's own stats only, and
``trace_reduce.load`` keeps none.  So this module reads the ``.xplane.pb``
itself: the protobuf wire format of ``XSpace`` (tsl/profiler/protobuf/
xplane.proto) is decoded by hand, which needs neither jax nor a generated
class — the driver process, which runs the layer-metric readers, never
imports jax.

``load`` gives the trace in ``trace_reduce``'s plain form plus a map from
an event's name (on a device's ``XLA Ops`` line: the instruction's HLO text)
to its scope path; the arithmetic is ``trace_reduce``'s.  A fused op has the
scope of the fusion's root instruction.
"""

from __future__ import annotations

import os
import struct

from benchmark import common, trace_reduce

SCOPE_STAT = "tf_op"
WINDOW_SPAN = "traced_window"      # kinds/fed_train.py's span of that name


# -- protobuf wire format ---------------------------------------------------

def _varint(buf, pos: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, pos
        shift += 7


def _fields(buf):
    """``(field number, wire type, value)`` of one message: a varint as an
    int, 64- and 32-bit fields as raw bytes, a length-delimited field as a
    memoryview of its bytes."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 1:
            value, pos = buf[pos:pos + 8], pos + 8
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire == 5:
            value, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield number, wire, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf) -> tuple[int, object]:
    """``XStat``: ``(metadata id, value)``; a ``ref_value`` comes back as
    ``("ref", id)`` for the caller to look up."""
    key, value = 0, None
    for number, _wire, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number in (5, 6):
            value = _text(v)
        elif number == 7:
            value = ("ref", v)
    return key, value


def _map_entry(buf) -> tuple[int, object]:
    key, value = 0, b""
    for number, _wire, v in _fields(buf):
        if number == 1:
            key = _signed(v)
        elif number == 2:
            value = v
    return key, value


def _plane(buf) -> dict:
    """``XPlane`` -> ``{"name", "lines": [{"name", "events": [(name,
    start_ns, duration_ns)]}], "scopes": {event name: scope path}}``."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for number, _wire, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            key, meta = _map_entry(v)
            event_meta[key] = meta
        elif number == 5:
            key, meta = _map_entry(v)
            stat_names[key] = next(
                (_text(x) for n, _w, x in _fields(meta) if n == 2), "")
    scope_stat = {k for k, n in stat_names.items() if n == SCOPE_STAT}
    names, scopes = {}, {}
    for key, meta in event_meta.items():
        event_name, scope = "", None
        for number, _wire, v in _fields(meta):
            if number == 2:
                event_name = _text(v)
            elif number == 5 and scope_stat:
                stat_key, value = _stat(v)
                if stat_key in scope_stat:
                    if isinstance(value, tuple):        # a ref to a name
                        value = stat_names.get(value[1], "")
                    scope = value
        names[key] = event_name
        if scope:
            scopes[event_name] = str(scope)
    out_lines = []
    for line in lines:
        line_name, timestamp_ns, events = "", 0, []
        for number, _wire, v in _fields(line):
            if number == 2:
                line_name = _text(v)
            elif number == 3:
                timestamp_ns = _signed(v)
            elif number == 4:
                events.append(v)
        decoded = []
        for event in events:
            meta_id = offset_ps = duration_ps = 0
            for number, _wire, v in _fields(event):
                if number == 1:
                    meta_id = _signed(v)
                elif number == 2:
                    offset_ps = _signed(v)
                elif number == 3:
                    duration_ps = _signed(v)
            # as tsl's XEventVisitor: whole nanoseconds
            decoded.append((names.get(meta_id, ""),
                            float(timestamp_ns + offset_ps // 1000),
                            float(duration_ps // 1000)))
        out_lines.append({"name": line_name, "events": decoded})
    return {"name": name, "lines": out_lines, "scopes": scopes}


_LOADED: dict[str, dict] = {}


def load(path: str) -> dict:
    """The xplane at ``path`` as ``trace_reduce``'s plain trace, each plane
    with the ``scopes`` of its events.  Kept per path: the readers of one
    run share one decoding."""
    key = os.path.abspath(path)
    if key not in _LOADED:
        with open(path, "rb") as f:
            space = memoryview(f.read())
        _LOADED.clear()
        _LOADED[key] = {"planes": [
            _plane(v) for number, _wire, v in _fields(space) if number == 1]}
    return _LOADED[key]


# -- the reduction ----------------------------------------------------------

def scope_seconds(trace: dict, window=None) -> dict[str, float]:
    """Device SELF time by scope path, in seconds, mean over devices: each
    op's own time (``trace_reduce.self_times``: a ``while`` holds its body on
    the same line) goes to the scope path of its instruction; ops without one
    go to ``""``.  Only ops that lie inside ``window`` (ns) count."""
    planes = trace_reduce.device_planes(trace)
    sums: dict[str, float] = {}
    for plane in planes:
        events = trace_reduce.line_events(plane, trace_reduce.OPS_LINE)
        if window:
            events = [e for e in events
                      if e[1] >= window[0] and e[1] + e[2] <= window[1]]
        scopes = plane.get("scopes", {})
        for name, t in trace_reduce.self_times(events):
            scope = scopes.get(name, "")
            sums[scope] = sums.get(scope, 0.0) + t
    return {k: v * 1e-9 / max(1, len(planes)) for k, v in sums.items()}


def in_scope(path: str, scope: str) -> bool:
    """Whether ``scope`` (``moe/experts``) is a run of whole components of
    the scope path (``jit(step)/loss_and_grad/transpose(jvp(moe))/moe/
    experts/dot_general``)."""
    return f"/{scope}/" in f"/{path}/"


def run_scope_seconds(run: dict) -> dict[str, float] | None:
    """``scope_seconds`` of the traced window of the run a layer-metric
    reader was handed; None where there is no trace, or no op in it carries
    a scope (a program without named scopes)."""
    if not run.get("trace") or not run["facts"].get("traced_steps"):
        return None
    path = common.find_xplane(os.path.join(
        common.WORK_DIR, "runs", run["cell"]["workload"], "trace"))
    if path is None:
        return None
    trace = load(path)
    sums = scope_seconds(trace,
                         trace_reduce.traced_window(trace, WINDOW_SPAN))
    return sums if any(sums) else None


def ms_per_step(run: dict, *scopes: str) -> float | None:
    """Milliseconds per traced step of the device ops inside any of
    ``scopes``; None where the trace has no op in them (the parent commit's
    program, a cell whose model lacks the layer)."""
    sums = run_scope_seconds(run)
    if sums is None:
        return None
    picked = [t for path, t in sums.items()
              if any(in_scope(path, s) for s in scopes)]
    if not picked:
        return None
    return 1e3 * sum(picked) / run["facts"]["traced_steps"]
