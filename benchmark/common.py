"""Shared pieces of the harness.  Importing this module never imports jax:
the driver process (``run.py``) uses the top half, the node process the
bottom half (every function there imports jax itself)."""

from __future__ import annotations

import importlib.util
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
# large run-time data (records, bundles, traces); .gitignore lists .bench_data/
WORK_DIR = os.path.join(ROOT, ".bench_data", "benchmark")


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(path: str = MANIFEST) -> dict:
    return read_json(path)


def load_module(folder: str, name: str, base: str = HERE):
    """``<base>/<folder>/<name>.py`` as a module, found by name alone: adding
    a configuration, a kind of traffic or a layer metric is adding a file."""
    path = os.path.join(base, folder, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {folder}/{name}.py under {base}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve_cell(workload: str, manifest_path: str = MANIFEST) -> dict:
    """Everything one cell is made of, as plain data: its ``workloads`` entry,
    its configuration file, its traffic file and the metrics that apply."""
    manifest = load_manifest(manifest_path)
    root = os.path.dirname(os.path.abspath(manifest_path))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    config_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    base = os.path.join(root, manifest["paths"][0])

    def applies(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "workload": workload,
        "chips": int(cell["chips"]),
        "config_name": cell["config"],
        "config": read_json(os.path.join(root, config_entry["file"])),
        "traffic_name": cell["traffic"],
        "traffic": read_json(os.path.join(base, "traffic",
                                          f"{cell['traffic']}.json")),
        "end_to_end": [m for m in manifest["end_to_end"] if applies(m)],
        "per_layer": [m for m in manifest["per_layer"] if applies(m)],
        "base": base,
    }


def peaks_for(device_kind: str, base: str = HERE) -> dict:
    """The chip's published peaks; a device that is not in the table is an
    error, never a default."""
    table = read_json(os.path.join(base, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (has {sorted(table)})")
    return table[device_kind]


def seeded_rng(seed: int, stream: str):
    """One numpy generator per (seed, named stream): weights, records, rows
    and pools never share draws, and the same seed repeats them exactly."""
    import zlib

    import numpy as np

    return np.random.default_rng([int(seed), zlib.crc32(stream.encode())])


# ---------------------------------------------------------------------------
# Node side (each function imports jax itself).
# ---------------------------------------------------------------------------

def device_facts() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def allocator_peak_bytes() -> int | None:
    """Highest ``peak_bytes_in_use`` over the devices (None: not reported)."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    if any(s is None for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def program_bytes(compiled) -> dict | None:
    """What the compiled program itself needs on each device (XLA's own
    accounting).  Its temporaries are not buffers the allocator statistics
    ever see (PR 21: 0.48 GB allocator peak beside 9.1 GB of temporaries)."""
    m = compiled.memory_analysis()
    if m is None:
        return None
    return {"arguments": int(m.argument_size_in_bytes),
            "outputs": int(m.output_size_in_bytes),
            "aliased": int(m.alias_size_in_bytes),
            "temporaries": int(m.temp_size_in_bytes)}


class CompileCounter:
    """Counts programs compiled OR loaded from the persistent cache (both
    stall the caller) through ``jax.monitoring``'s backend-compile event."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.count += 1


class Spans:
    """The benchmark's own spans: each is a ``jax.profiler.TraceAnnotation``
    (so it lands on the profiler's timeline while a trace is on) and a
    host-clock total (so shares are known with the profiler off)."""

    def __init__(self):
        self.totals: dict[str, float] = {}
        self.counts: dict[str, int] = {}

    def span(self, name: str):
        return _Span(self, name)

    def reset(self) -> None:
        self.totals.clear()
        self.counts.clear()


class _Span:
    def __init__(self, owner: Spans, name: str):
        import jax

        self._owner, self._name = owner, name
        self._annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        totals, counts = self._owner.totals, self._owner.counts
        totals[self._name] = totals.get(self._name, 0.0) + dt
        counts[self._name] = counts.get(self._name, 0) + 1
        return False


def counter_delta(before: dict, after: dict) -> dict:
    """Counters of ``telemetry.snapshot()`` that moved, as differences."""
    a, b = after.get("counters", {}), before.get("counters", {})
    return {k: a[k] - b.get(k, 0) for k in a if a[k] != b.get(k, 0)}


def find_xplane(trace_dir: str) -> str | None:
    import glob

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None
