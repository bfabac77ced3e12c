"""How many times a Pallas kernel ran under a ``jax.named_scope`` in a traced
step, from the run's xplane.

A share of a roofline divides the least time of the kernel's calls by the
scope's measured time; how often the kernel ran is the program's to decide (a
``remat`` policy runs a forward once or twice), so the count comes from the
trace, as ``flash_fwd_roofline`` takes ``pallas_calls``, and not from a key of
the configuration.  The decoding and the window are ``scope_times``'s."""

from __future__ import annotations

import os

from benchmark import common, scope_times, trace_reduce


def kernels_per_step(run: dict, scope: str) -> float | None:
    """Executions a traced step of the Pallas kernels whose instruction lies
    inside ``scope``, mean over devices; None where there is no trace or no
    such kernel in it (the parent commit's program)."""
    if not run.get("trace") or not run["facts"].get("traced_steps"):
        return None
    path = common.find_xplane(os.path.join(
        common.WORK_DIR, "runs", run["cell"]["workload"], "trace"))
    if path is None:
        return None
    trace = scope_times.load(path)
    window = trace_reduce.traced_window(trace, scope_times.WINDOW_SPAN)
    planes = trace_reduce.device_planes(trace)
    calls = 0
    for plane in planes:
        scopes = plane.get("scopes", {})
        for name, start, duration in trace_reduce.line_events(
                plane, trace_reduce.OPS_LINE):
            if window and not (start >= window[0]
                               and start + duration <= window[1]):
                continue
            if (trace_reduce.is_pallas_kernel(name)
                    and scope_times.in_scope(scopes.get(name, ""), scope)):
                calls += 1
    if not calls:
        return None
    return calls / max(1, len(planes)) / run["facts"]["traced_steps"]
