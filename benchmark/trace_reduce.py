"""From a profiler trace to numbers.  The one reduction every PR uses.

``load`` turns an ``.xplane.pb`` (``jax.profiler.ProfileData``) into plain
Python data; every other function works on that data, so the arithmetic is
testable without a trace file or a device:

    trace = {"planes": [{"name": str, "lines": [
                {"name": str, "events": [(name, start_ns, dur_ns), ...]}]}]}

What a TPU trace looks like (read off a v5e trace, PR 22; a copy is in
``testdata/``): one plane per chip named ``/device:TPU:<n>`` with the lines
``XLA Modules`` (one event per program execution), ``XLA Ops`` (one event
per HLO instruction executed, whose NAME IS THE INSTRUCTION'S HLO TEXT,
``%name = shape opcode(operands), attributes``) and ``Async XLA Ops``; and a
``/host:CPU`` plane with one line per thread, where ``TraceAnnotation``s
appear on the thread that made them.  The device clock ran about a
millisecond ahead of the host clock in that trace, so a gap is attributed to
a host span only at that precision.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "all-to-all", "collective-permute",
                      "collective-broadcast")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
ATTRIBUTED_GAPS = 64


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return {"planes": [
        {"name": plane.name, "lines": [
            {"name": line.name,
             "events": [(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]}
            for line in plane.lines]}
        for plane in data.planes]}


# -- HLO instruction text ---------------------------------------------------

def op_name(text: str) -> str:
    """``%fusion.3`` of ``%fusion.3 = f32[8] fusion(...), kind=kLoop``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def opcode(text: str) -> str:
    """``fusion`` / ``custom-call`` / ``all-reduce-start`` ... ('' if the
    event is not an HLO instruction)."""
    if " = " not in text:
        return ""
    m = _OPCODE.search(text.split(" = ", 1)[1])
    return m.group(1) if m else ""


def is_collective(text: str) -> bool:
    code = opcode(text)
    if any(code.startswith(c) for c in COLLECTIVE_OPCODES):
        return True
    # a fused collective keeps its kind in the instruction's name
    return code == "fusion" and any(
        op_name(text).startswith(c) for c in COLLECTIVE_OPCODES)


def is_pallas_kernel(text: str) -> bool:
    return PALLAS_TARGET in text


# -- intervals --------------------------------------------------------------

def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint cover of ``[(start, end), ...]``."""
    out: list[list[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """The part of cover ``a`` that cover ``b`` does not touch."""
    out, b = [], list(b)
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, window) -> list[tuple[float, float]]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


# -- planes and lines -------------------------------------------------------

def device_planes(trace: dict) -> list[dict]:
    planes = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    return sorted(planes,
                  key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(2)))


def line_events(plane: dict, line_name: str) -> list[tuple]:
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def op_intervals(plane: dict, keep=None) -> list[tuple[float, float]]:
    return [(s, s + d) for name, s, d in line_events(plane, OPS_LINE)
            if keep is None or keep(name)]


def host_spans(trace: dict, names) -> list[tuple[str, float, float]]:
    """``(name, start, end)`` of the host events called one of ``names``."""
    names = set(names)
    out = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            out += [(n, s, s + d) for n, s, d in line["events"] if n in names]
    return sorted(out, key=lambda x: x[1])


def traced_window(trace: dict, span_name: str | None = None):
    """The window the numbers refer to: the host span ``span_name`` if the
    trace has one, else first device op start to last device op end."""
    if span_name:
        spans = host_spans(trace, [span_name])
        if spans:
            return spans[0][1], spans[-1][2]
    ops = [i for p in device_planes(trace) for i in op_intervals(p)]
    if not ops:
        return None
    return min(s for s, _ in ops), max(e for _, e in ops)


# -- the reductions ---------------------------------------------------------

def busy(trace: dict, window=None) -> dict | None:
    """Device busy seconds: per device, the union of the intervals in which
    an operation ran, inside ``window`` (ns); ``busy_s`` is the mean over
    devices.  None when no operation ran on any device."""
    window = window or traced_window(trace)
    planes = device_planes(trace)
    if window is None or not planes:
        return None
    per_device = [total(clip(union(op_intervals(p)), window)) * 1e-9
                  for p in planes]
    if not any(per_device):
        return None
    return {"busy_s": sum(per_device) / len(per_device),
            "window_s": (window[1] - window[0]) * 1e-9,
            "per_device_busy_s": per_device}


def self_times(events) -> list[tuple[str, float]]:
    """Each event's own time: its duration minus that of events nested in it
    (a ``while`` holds its body's instructions on the same line)."""
    out = []
    stack: list[list] = []          # [name, end, self]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    out += [(n, t) for n, _, t in stack]
    return out


def top_ops(trace: dict, n: int = 10, window=None) -> list[list]:
    """The device operations that took most (self) time, in seconds, mean
    over devices: ``[[name, seconds], ...]``."""
    planes = device_planes(trace)
    sums: dict[str, float] = {}
    for plane in planes:
        events = line_events(plane, OPS_LINE)
        if window:
            events = [e for e in events
                      if e[1] >= window[0] and e[1] + e[2] <= window[1]]
        for name, t in self_times(events):
            key = op_label(name)
            sums[key] = sums.get(key, 0.0) + t
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9 / max(1, len(planes))] for k, v in ranked]


def op_label(text: str) -> str:
    """Short, stable label of an instruction: its name, and for a Pallas
    kernel or a collective what it is."""
    label = op_name(text)
    if is_pallas_kernel(text):
        label = f"pallas:{label}"
    elif is_collective(text):
        label = f"collective:{label}"
    return label[:80]


def ops_seconds(trace: dict, keep, window=None) -> float:
    """Seconds of the operations ``keep(text)`` selects, mean over devices."""
    planes = device_planes(trace)
    if not planes:
        return 0.0
    t = 0.0
    for plane in planes:
        cover = union(op_intervals(plane, keep))
        t += total(clip(cover, window) if window else cover)
    return t * 1e-9 / len(planes)


def ops_count(trace: dict, keep, window=None) -> float:
    """How many operations ``keep`` selects, mean over devices."""
    planes = device_planes(trace)
    if not planes:
        return 0.0
    n = 0
    for plane in planes:
        for name, s, d in line_events(plane, OPS_LINE):
            if keep(name) and (not window
                               or (s >= window[0] and s + d <= window[1])):
                n += 1
    return n / len(planes)


def collectives(trace: dict, window=None) -> dict | None:
    """Collective time, and the part of it with no compute running on that
    device (exposed).  Seconds, mean over devices; None without collectives."""
    planes = device_planes(trace)
    totals, exposed = [], []
    for plane in planes:
        coll = union(op_intervals(plane, is_collective))
        comp = union(op_intervals(plane, lambda t: not is_collective(t)))
        if window:
            coll, comp = clip(coll, window), clip(comp, window)
        totals.append(total(coll) * 1e-9)
        exposed.append(total(subtract(coll, comp)) * 1e-9)
    if not any(totals):
        return None
    return {"collective_s": sum(totals) / len(totals),
            "exposed_s": sum(exposed) / len(exposed),
            "per_device_collective_s": totals}


def _program_events(trace: dict, window=None) -> list[tuple]:
    """Program executions on the first device (the ``XLA Modules`` line)
    that lie inside ``window``."""
    planes = device_planes(trace)
    if not planes:
        return []
    return [e for e in line_events(planes[0], MODULES_LINE)
            if not window or (e[1] >= window[0]
                              and e[1] + e[2] <= window[1])]


def program_runs(trace: dict, window=None) -> int:
    return len(_program_events(trace, window))


def program_run_ms(trace: dict, window=None, limit: int = 64) -> list[list]:
    """``[start_ms, duration_ms]`` of the first ``limit`` program
    executions, start counted from the window's start."""
    origin = window[0] if window else 0.0
    return [[(s - origin) * 1e-6, d * 1e-6]
            for _n, s, d in _program_events(trace, window)[:limit]]


def idle_gaps(trace: dict, span_names, window=None, n: int = 10,
              ignore=()) -> list[list]:
    """The idle time of the first device by what the host was doing:
    ``[[name, seconds], ...]``, largest first.  Each gap between device
    operations goes to the benchmark span (one of ``span_names``) that
    covers most of it; a gap no span covers goes to
    ``unattributed:<function>``: the shortest host event, on any thread, that
    still covers most of the gap (names in ``ignore`` are skipped), or
    ``unattributed:unknown``."""
    planes = device_planes(trace)
    window = window or traced_window(trace)
    if not planes or window is None:
        return []
    cover = clip(union(op_intervals(planes[0])), window)
    gaps = subtract([window], cover)
    spans = host_spans(trace, span_names)
    functions = []
    for plane in trace["planes"]:
        if plane["name"] == HOST_PLANE:
            for line in plane["lines"]:
                functions += [(nm, s, s + d) for nm, s, d in line["events"]
                              if nm not in span_names and nm not in ignore]
    # only the longest gaps are looked up (each costs a pass over the host
    # events); the many short ones between operations are summed
    gaps.sort(key=lambda g: g[0] - g[1])
    sums: dict[str, float] = {}
    short = total(gaps[ATTRIBUTED_GAPS:])
    if short:
        sums["short gaps between operations"] = short
    for gs, ge in gaps[:ATTRIBUTED_GAPS]:
        best, best_overlap = None, 0.0
        for name, s, e in spans:
            overlap = min(e, ge) - max(s, gs)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        if best is None or best_overlap < 0.5 * (ge - gs):
            inner, inner_overlap = "unknown", 0.0
            for name, s, e in functions:
                overlap = min(e, ge) - max(s, gs)
                # the innermost function that still covers most of the gap
                if overlap >= 0.5 * (ge - gs) and (
                        inner_overlap == 0.0 or e - s < inner_overlap):
                    inner, inner_overlap = name, e - s
            best = f"unattributed:{inner.lstrip('$').strip()}"
        sums[best] = sums.get(best, 0.0) + (ge - gs)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[k[:80], v * 1e-9] for k, v in ranked]


def summarize(trace: dict, span_names=(), window_span: str | None = None,
              ) -> dict | None:
    """Everything the harness and the layer-metric readers use, in one
    JSON-safe dict.  None when no operation ran on a device."""
    window = traced_window(trace, window_span)
    b = busy(trace, window)
    if b is None:
        return None
    return {
        **b,
        "devices": len(device_planes(trace)),
        "program_runs": program_runs(trace, window),
        "program_run_ms": program_run_ms(trace, window),
        "device_ops": top_ops(trace, 10, window),
        "idle_gaps": idle_gaps(trace, list(span_names), window,
                               ignore=(window_span,)),
        "collectives": collectives(trace, window),
        "pallas_s": ops_seconds(trace, is_pallas_kernel, window),
        "pallas_calls": ops_count(trace, is_pallas_kernel, window),
    }
