"""One account of a traced LM step's device self-time, closed by construction.

``scope_times.run_scope_seconds`` gives the traced window's device SELF time
by scope path (an op's ``op_name``: the ``jax.named_scope`` and flax module
components it was traced under).  Self-times tile the step: an op's time is
in exactly one path.  This module puts every path into exactly ONE bucket of
an ordered list, so the buckets tile the step too and what no reader owned is
a number, not a hand count off an xplane:

- ``BUCKETS``: a name and the scope components that own it, in order; a path
  goes to the FIRST bucket one of whose components is a run of whole
  components of it (``scope_times.in_scope``).  The order puts a nested scope
  with its most specific owner: ``attn/mla/project/q_proj`` is the latent
  projections', ``attn/q_proj`` the attention projections',
  ``mtp/lm_head_loss`` the head's, ``moe/shared/shared/mlp`` the experts'.
- ``UNOWNED``: a path with components none of which a bucket names (the
  program opened a scope, or flax named a module, that this list has not
  heard of: name it here).
- ``UNSCOPED``: the empty path.  The compiler writes some instructions with
  no ``op_name`` whichever scope traced the work (on the v5e: a multi-output
  fusion it merges of siblings, as RoPE's two halves; asynchronous copies;
  zero-filled buffers; layout copies); the program cannot name those, so the
  account measures them.

A fusion carries its ROOT's scope (``scope_times``): work fused across a
scope's edge is booked whole to one side, here as in every scope reader.

``python3 -m benchmark.step_account <run directory>`` prints the account of
a traced run (``.bench_data/benchmark/runs/<cell>``, a profiler's trace
directory, or one ``.xplane.pb``) and the longest unowned and unscoped ops by
XLA op name.
"""

from __future__ import annotations

import functools
import os
import sys

from benchmark import common, scope_times, trace_reduce

UNOWNED = "unowned"
UNSCOPED = "unscoped"
REMAT = "rematted_computation"     # jax.checkpoint's second forward

# (bucket, the scope components that own it, the metric that reads it)
BUCKETS: tuple[tuple[str, tuple[str, ...], str], ...] = (
    ("optimizer", ("optimizer_update",), "moe_optimizer_ms"),
    ("head", ("lm_head_loss",), "lm_head_loss_ms"),
    ("embed", ("embed",), "lm_embed_ms"),
    ("attention kernels", ("flash_fwd", "flash_bwd", "flash_fwd_window",
                           "flash_bwd_window"),
     "bd_flash_fwd_ms, flash_bwd_ms, swa_flash_*_ms"),
    ("sparse attention", ("dsa/index", "dsa/select", "dsa/attend",
                          "dsa/index_loss"), "dsa_*_ms"),
    ("latent projections", ("mla/project",), "mla_project_ms"),
    ("attention projections", ("q_proj", "k_proj", "v_proj", "o_proj",
                               "qk_norm"), "lm_attn_proj_ms"),
    ("attention, the rest", ("attention", "attn"), "lm_attn_rest_ms"),
    ("experts", ("moe/router", "moe/dispatch", "moe/combine", "moe/experts",
                 "moe/shared", "moe/latent", "moe"), "moe_*_ms"),
    ("dense MLP", ("mlp",), "lm_mlp_ms"),
    ("state-space mixer", ("ssm",), "ssm_mixer_ms"),
    ("residual streams", ("hc/maps", "hc/pre", "hc/post", "hc/ends"),
     "hc_*_ms"),
    ("corruption", ("diffusion/corrupt",), "bd_corrupt_ms"),
    ("norms and glue", ("attn_norm", "mlp_norm", "norm", "final_norm",
                        "mtp_hnorm", "mtp_enorm", "mtp_norm", "mtp_eh_proj",
                        "mtp", "residual", "loss_terms"),
     "lm_glue_ms"),
)
NAMES = tuple(name for name, _c, _m in BUCKETS) + (UNOWNED, UNSCOPED)


@functools.lru_cache(maxsize=None)
def bucket_of(path: str) -> str:
    """The ONE bucket of a scope path: first match in ``BUCKETS``' order."""
    if not path:
        return UNSCOPED
    for name, components, _metric in BUCKETS:
        if any(scope_times.in_scope(path, c) for c in components):
            return name
    return UNOWNED


def account(sums: dict[str, float]) -> dict[str, float]:
    """Seconds by bucket, every bucket of ``NAMES`` present: the partition
    of ``sums`` (seconds by scope path), so the values add up to its total."""
    out = dict.fromkeys(NAMES, 0.0)
    for path, seconds in sums.items():
        out[bucket_of(path)] += seconds
    return out


def remat_seconds(sums: dict[str, float]) -> float:
    """Seconds of every op under ``rematted_computation``, whatever bucket
    owns it."""
    return sum(t for path, t in sums.items()
               if scope_times.in_scope(path, REMAT))


# -- the readers' halves ------------------------------------------------------

_LAST: list = [None, None]          # the run last read, and its sums


def _sums(run: dict) -> dict[str, float] | None:
    """``scope_times.run_scope_seconds(run)``, computed once for the ten
    readers of one run (the harness hands them the same object)."""
    if _LAST[0] is not run:
        _LAST[:] = [run, scope_times.run_scope_seconds(run)]
    return _LAST[1]


def bucket_ms(run: dict, name: str) -> float | None:
    """Milliseconds a traced step of the bucket ``name``; None where the run
    has no trace, no op of it carries a scope (``run_scope_seconds``), or no
    path of the trace lands in the bucket.  The two remainders read 0 there:
    a step with nothing unowned has a reading, not a gap (the dense LM's)."""
    sums = _sums(run)
    if sums is None:
        return None
    picked = [t for path, t in sums.items() if bucket_of(path) == name]
    if not picked and name not in (UNOWNED, UNSCOPED):
        return None
    return 1e3 * sum(picked) / run["facts"]["traced_steps"]


def remat_ms(run: dict) -> float | None:
    """Milliseconds a traced step under ``rematted_computation``; None where
    the program rematerialises nothing."""
    sums = _sums(run)
    seconds = remat_seconds(sums) if sums else 0.0
    if not seconds:
        return None
    return 1e3 * seconds / run["facts"]["traced_steps"]


def closure(run: dict) -> float | None:
    """The buckets' sum over the device-busy time of the traced window
    (``lm_step_device_ms`` x traced steps), in percent: 100 where self-times
    tile the step.  None without a trace."""
    sums = _sums(run)
    if sums is None:
        return None
    busy = run["trace"].get("busy_s")
    if not busy:
        return None
    return 100.0 * sum(account(sums).values()) / busy


# -- the operator's view ------------------------------------------------------

def _ops_by_bucket(trace: dict, window) -> dict[str, dict[tuple, float]]:
    """``{bucket: {(XLA op name, scope path): self seconds}}``, mean over
    devices, of the two remainder buckets."""
    planes = trace_reduce.device_planes(trace)
    out: dict[str, dict[tuple, float]] = {UNOWNED: {}, UNSCOPED: {}}
    for plane in planes:
        events = trace_reduce.line_events(plane, trace_reduce.OPS_LINE)
        if window:
            events = [e for e in events
                      if e[1] >= window[0] and e[1] + e[2] <= window[1]]
        scopes = plane.get("scopes", {})
        for name, t in trace_reduce.self_times(events):
            path = scopes.get(name, "")
            bucket = bucket_of(path)
            if bucket in out:
                key = (trace_reduce.op_name(name), path)
                out[bucket][key] = out[bucket].get(key, 0.0) + t
    n = max(1, len(planes))
    return {b: {k: v * 1e-9 / n for k, v in ops.items()}
            for b, ops in out.items()}


def report(run_dir: str, steps: int | None = None, top: int = 10) -> str:
    """The account of the traced run under ``run_dir`` as text; ``steps``
    defaults to the program executions inside the traced window."""
    path = run_dir if os.path.isfile(run_dir) else (
        common.find_xplane(os.path.join(run_dir, "trace"))
        or common.find_xplane(run_dir))
    if path is None:
        raise SystemExit(f"no .xplane.pb under {run_dir}")
    trace = scope_times.load(path)
    window = trace_reduce.traced_window(trace, scope_times.WINDOW_SPAN)
    sums = scope_times.scope_seconds(trace, window)
    steps = steps or trace_reduce.program_runs(trace, window)
    per = 1e3 / steps if steps else 1e3
    unit = "ms a step" if steps else "ms in the window"
    total = sum(sums.values())
    lines = [f"{path}", f"traced steps: {steps or 'unknown'}; device "
             f"self-time {total * per:.3f} {unit}",
             f"{'bucket':<24}{unit:>18}{'share':>9}"]
    rows = list(account(sums).items()) + [(f"({REMAT})", remat_seconds(sums))]
    for name, seconds in rows:
        lines.append(f"{name:<24}{seconds * per:>18.3f}"
                     f"{100 * seconds / max(total, 1e-30):>8.2f}%")
    lines[-1] += "  overlaps the buckets"
    for bucket, ops in _ops_by_bucket(trace, window).items():
        lines.append(f"-- longest {bucket} ops ({unit})")
        for (op, scope), seconds in sorted(
                ops.items(), key=lambda kv: -kv[1])[:top]:
            lines.append(f"{seconds * per:>10.3f}  {op:<44} {scope}")
    return "\n".join(lines)


def main(argv: list[str]) -> None:
    if not argv or argv[0] in ("-h", "--help"):
        raise SystemExit(
            "usage: python3 -m benchmark.step_account <run directory or "
            ".xplane.pb> [traced steps]")
    print(report(argv[0], int(argv[1]) if len(argv) > 1 else None))


if __name__ == "__main__":
    main(sys.argv[1:])
