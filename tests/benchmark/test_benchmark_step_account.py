"""The step's account (ISSUE 50): ``benchmark/step_account.py`` puts every
scope path of a traced LM step into exactly ONE bucket, so the buckets tile
the step's device self-time; ten readers read a bucket each (or the whole
list) and find nothing in a run without a trace.  Hand-made paths in the
shapes the accepted tests' ``SUMS`` have, and the xplane kept with the
benchmark.  Nothing runs on a chip."""

from __future__ import annotations

import os

import pytest

from benchmark import common, scope_times, step_account

STEP = "jit(step)/jit(main)/loss_and_grad/"
FWD, BWD = "jvp(Transformer)/", "transpose(jvp(Transformer))/"
# jax.checkpoint's second forward, as a remat cell's backward names it
REMAT = BWD + "loss_and_grad/jvp(Transformer)/checkpoint/rematted_computation/"
OPT = "jit(step)/jit(main)/optimizer_update/"

# cell's shape of path -> {scope path: (seconds, the bucket that owns it)}
PATHS = {
    "kanana2": {
        STEP + FWD + "block_1/attn/mla/project/q_proj/dot_general:":
            (300e-6, "latent projections"),
        STEP + BWD + "block_1/attn/mla/project/o_proj/dot_general:":
            (500e-6, "latent projections"),
        STEP + FWD + "block_1/attn/attention/flash_fwd/"
        "jit(_flash_fwd_pallas)/pallas_call:": (700e-6, "attention kernels"),
        STEP + FWD + "block_1/attn/attention/concatenate:":
            (40e-6, "attention, the rest"),
        STEP + FWD + "block_1/moe/shared/shared/mlp/gate_proj/dot_general:":
            (60e-6, "experts"),
        STEP + FWD + "block_1/moe/moe/router/top_k:": (20e-6, "experts"),
        STEP + FWD + "block_0/mlp/mlp/down_proj/dot_general:":
            (400e-6, "dense MLP"),
        STEP + BWD + "block_0/mlp_norm/mul:": (15e-6, "norms and glue"),
        STEP + FWD + "block_0/residual/add:": (10e-6, "norms and glue"),
        STEP + "jvp(lm_loss)/lm_head_loss/dot_general:": (900e-6, "head"),
        STEP + "transpose(jvp(lm_loss))/lm_head_loss/mul:": (5e-6, "head"),
        STEP + "jvp(lm_loss)/loss_terms/add:": (1e-6, "norms and glue"),
        STEP + FWD + "embed/gather:": (30e-6, "embed"),
        OPT + "mul:": (800e-6, "optimizer"),
        "": (30e-6, "unscoped"),
    },
    "smallthinker": {
        STEP + FWD + "block_0/attn/attention/flash_fwd/pallas_call:":
            (1600e-6, "attention kernels"),
        REMAT + "block_1/attn/attention/flash_fwd_window/transpose:":
            (100e-6, "attention kernels"),
        STEP + BWD + "block_1/attn/attention/flash_bwd_window/pallas_call:":
            (2000e-6, "attention kernels"),
        STEP + FWD + "block_1/attn/q_proj/dot_general:":
            (120e-6, "attention projections"),
        REMAT + "block_1/attn/o_proj/dot_general:":
            (140e-6, "attention projections"),
        REMAT + "block_1/attn/attention/reduce_precision:":
            (30e-6, "attention, the rest"),
        REMAT + "block_1/moe/moe/dispatch/gather:": (80e-6, "experts"),
        REMAT + "block_1/attn_norm/mul:": (9e-6, "norms and glue"),
        REMAT + "block_1/residual/add:": (4e-6, "norms and glue"),
        STEP + BWD + "final_norm/mul:": (3e-6, "norms and glue"),
        STEP + "jvp(lm_loss)/lm_head_loss/while/body/dot_general:":
            (340e-6, "head"),
        # what XLA's scatter expander makes of the embedding's gradient
        "": (220e-6, "unscoped"),
    },
    "nemotron3": {
        STEP + FWD + "block_0/ssm/ssm/ssm/in_proj/in_proj/dot_general:":
            (300e-6, "state-space mixer"),
        STEP + BWD + "block_0/ssm/ssm/ssm/scan/checkpoint/ssd/state/while:":
            (1200e-6, "state-space mixer"),
        STEP + FWD + "block_0/norm/rsqrt:": (8e-6, "norms and glue"),
        STEP + FWD + "block_1/moe/moe/latent/latent_down/dot_general:":
            (60e-6, "experts"),
        STEP + FWD + "block_1/moe/shared/shared/mlp/up_proj/dot_general:":
            (700e-6, "experts"),
        STEP + FWD + "block_1/moe/moe/experts/pallas_call:":
            (50e-6, "experts"),
        STEP + FWD + "block_7/attn/k_proj/dot_general:":
            (20e-6, "attention projections"),
        STEP + FWD + "block_7/attn/attention/flash_fwd/pallas_call:":
            (25e-6, "attention kernels"),
        STEP + BWD + "block_7/residual/add_any:": (6e-6, "norms and glue"),
        "": (50e-6, "unscoped"),
    },
    "keye": {
        STEP + "jvp(sparse_lm)/Transformer/block_0/attn/dsa/index/"
        "wq/dot_general:": (300e-6, "sparse attention"),
        STEP + "transpose(jvp(sparse_lm))/Transformer/loss_and_grad/"
        "jvp(sparse_lm)/Transformer/checkpoint/rematted_computation/block_0/"
        "attn/dsa/index/pallas_call:": (500e-6, "sparse attention"),
        STEP + "transpose(jvp(sparse_lm))/Transformer/block_1/attn/dsa/"
        "index_loss/mul:": (200e-6, "sparse attention"),
        STEP + "jvp(sparse_lm)/Transformer/block_1/attn/q_proj/dot_general:":
            (400e-6, "attention projections"),
        STEP + "jvp(sparse_lm)/Transformer/block_1/attn/qk_norm/mul:":
            (70e-6, "attention projections"),
        STEP + "jvp(sparse_lm)/lm_head_loss/dot_general:": (50e-6, "head"),
        STEP + "jvp(sparse_lm)/loss_terms/add:": (1e-6, "norms and glue"),
        # the wrapper's own few ops: no bucket claims the wrapper
        STEP + "jvp(sparse_lm)/reduce_sum:": (1e-6, "unowned"),
        "": (30e-6, "unscoped"),
    },
    "sdar": {
        STEP + "jvp(block_diffusion)/Transformer/block_0/attn/attention/"
        "flash_fwd/pallas_call:": (700e-6, "attention kernels"),
        STEP + "jvp(block_diffusion)/diffusion/corrupt/threefry2x32:":
            (30e-6, "corruption"),
        STEP + "transpose(jvp(block_diffusion))/Transformer/block_0/attn/"
        "attention/flash_bwd/pallas_call:": (900e-6, "attention kernels"),
        STEP + "jvp(block_diffusion)/lm_head_loss/dot_general:":
            (90e-6, "head"),
        STEP + "jvp(block_diffusion)/Transformer/embed/gather:":
            (10e-6, "embed"),
        "": (30e-6, "unscoped"),
    },
    "xing4": {
        STEP + FWD + "block_1/block_1._hyper_connected/hc_attn/hc/maps/div:":
            (100e-6, "residual streams"),
        STEP + BWD + "block_1/block_1._hyper_connected/hc/post/concatenate:":
            (500e-6, "residual streams"),
        STEP + FWD + "hc/ends/tile:": (40e-6, "residual streams"),
        STEP + FWD + "mtp/mtp_block/mtp_block._hyper_connected/attn/mla/"
        "project/q_b_proj/dot_general:": (150e-6, "latent projections"),
        STEP + FWD + "mtp/mtp_eh_proj/dot_general:":
            (50e-6, "norms and glue"),
        STEP + FWD + "mtp/concatenate:": (5e-6, "norms and glue"),
        STEP + FWD + "mtp/embed/gather:": (10e-6, "embed"),
        STEP + "jvp(mtp_loss)/mtp/lm_head_loss/dot_general:":
            (100e-6, "head"),
        STEP + "transpose(jvp(mtp_loss))/mtp/lm_head_loss/mul:":
            (2e-6, "head"),
        STEP + "jvp(mtp_loss)/loss_terms/mul:": (1e-6, "norms and glue"),
        "": (30e-6, "unscoped"),
    },
}
NEW = ["lm_head_loss_ms", "lm_embed_ms", "lm_attn_proj_ms", "lm_attn_rest_ms",
       "lm_mlp_ms", "lm_glue_ms", "lm_unowned_ms", "lm_unscoped_ms",
       "lm_account_closure", "lm_remat_ms"]
LM_CELLS = [w["name"] for w in common.load_manifest()["workloads"]
            if not w["name"].startswith("resnet50")]


def _sums(shape: str) -> dict[str, float]:
    return {path: seconds for path, (seconds, _b) in PATHS[shape].items()}


def _run(monkeypatch, sums, busy_s=1.0):
    monkeypatch.setattr(scope_times, "run_scope_seconds", lambda run: sums)
    return {"cell": {"workload": LM_CELLS[0]}, "trace": {"busy_s": busy_s},
            "facts": {"traced_steps": 2}}


# -- the partition ------------------------------------------------------------

@pytest.mark.parametrize("shape", sorted(PATHS))
def test_every_path_lands_in_exactly_one_bucket_and_they_sum_to_the_total(
        shape):
    sums = _sums(shape)
    for path, (_seconds, bucket) in PATHS[shape].items():
        assert step_account.bucket_of(path) == bucket, path
        # exactly one: of the buckets that would match, the first in order
        owners = [name for name, components, _m in step_account.BUCKETS
                  if any(scope_times.in_scope(path, c) for c in components)]
        assert owners[:1] == ([] if bucket in ("unowned", "unscoped")
                              else [bucket])
    account = step_account.account(sums)
    assert tuple(account) == step_account.NAMES
    assert sum(account.values()) == pytest.approx(sum(sums.values()),
                                                  rel=1e-12)
    for bucket in step_account.NAMES:
        assert account[bucket] == pytest.approx(sum(
            s for s, b in PATHS[shape].values() if b == bucket))


@pytest.mark.parametrize("path,bucket", [
    # a nested scope goes to its most specific owner
    ("block_1/attn/mla/project/q_proj/dot_general:", "latent projections"),
    ("block_1/attn/q_proj/dot_general:", "attention projections"),
    ("mtp/lm_head_loss/dot_general:", "head"),
    ("lm_head_loss/lm_head/dot_general:", "head"),
    ("block_1/attn/dsa/index/wq/dot_general:", "sparse attention"),
    ("block_1/attn/attention/flash_bwd/mul:", "attention kernels"),
    ("block_1/attn/attention/mul:", "attention, the rest"),
    ("block_1/attn/reshape:", "attention, the rest"),
    ("block_1/moe/shared/shared/mlp/mul:", "experts"),
    ("block_1/moe/moe/add:", "experts"),
    ("block_0/mlp/mlp/mul:", "dense MLP"),
    ("mtp/mtp_block/mlp/mlp/mul:", "dense MLP"),
    ("mtp/mtp_hnorm/mul:", "norms and glue"),
    ("optimizer_update/lm_head_loss/mul:", "optimizer"),
    # a transform's name is no whole component: what the parent's loss was
    ("jit(step)/loss_and_grad/jvp(lm_head_loss)/dot_general:", "unowned"),
    ("jit(step)/add:", "unowned"),
    ("", "unscoped"),
])
def test_first_match_order_holds(path, bucket):
    assert step_account.bucket_of(path) == bucket


def test_bucket_names_and_components_are_each_listed_once():
    names = [name for name, _c, _m in step_account.BUCKETS]
    assert len(set(names)) == len(names)
    components = [c for _n, cs, _m in step_account.BUCKETS for c in cs]
    assert len(set(components)) == len(components)
    assert step_account.NAMES[-2:] == ("unowned", "unscoped")


def test_the_remat_reading_overlaps_the_buckets():
    sums = _sums("smallthinker")
    expected = sum(s for p, s in sums.items() if "rematted_computation" in p)
    assert expected > 0
    assert step_account.remat_seconds(sums) == pytest.approx(expected)
    # it is no bucket: the account's total does not hold it twice
    assert sum(step_account.account(sums).values()) == pytest.approx(
        sum(sums.values()))
    assert step_account.remat_seconds(_sums("kanana2")) == 0.0


# -- the readers --------------------------------------------------------------

@pytest.mark.parametrize("metric,shape,expected", [
    ("lm_head_loss_ms", "kanana2", 0.4525),     # 905 us over two steps
    ("lm_head_loss_ms", "xing4", 0.051),        # the MTP pass is the head's
    ("lm_embed_ms", "kanana2", 0.015),
    ("lm_attn_proj_ms", "smallthinker", 0.13),  # remat's second forward too
    ("lm_attn_proj_ms", "keye", 0.235),         # q_proj and qk_norm
    ("lm_attn_rest_ms", "kanana2", 0.02),
    ("lm_mlp_ms", "kanana2", 0.2),              # not the shared expert's mlp
    ("lm_glue_ms", "kanana2", 0.013),
    ("lm_glue_ms", "xing4", 0.028),
    ("lm_unowned_ms", "keye", 0.0005),
    ("lm_unscoped_ms", "smallthinker", 0.11),
    ("lm_remat_ms", "smallthinker", 0.1815),
])
def test_readers_read_their_bucket_per_traced_step(monkeypatch, metric, shape,
                                                   expected):
    reader = common.load_module("layer_metrics", metric)
    assert reader.read(_run(monkeypatch, _sums(shape))) == pytest.approx(
        expected)


def test_closure_is_the_buckets_sum_over_the_busy_time(monkeypatch):
    reader = common.load_module("layer_metrics", "lm_account_closure")
    sums = _sums("kanana2")
    total = sum(sums.values())
    assert reader.read(_run(monkeypatch, sums, busy_s=total)) == \
        pytest.approx(100.0)
    assert reader.read(_run(monkeypatch, sums, busy_s=2 * total)) == \
        pytest.approx(50.0)
    assert reader.read(_run(monkeypatch, sums, busy_s=0.0)) is None


@pytest.mark.parametrize("metric", NEW)
def test_new_readers_find_nothing_without_a_trace_or_their_components(
        monkeypatch, metric):
    reader = common.load_module("layer_metrics", metric)
    assert reader.read(_run(monkeypatch, None)) is None
    run = _run(monkeypatch, _sums("sdar"))
    monkeypatch.undo()
    assert reader.read({**run, "trace": None}) is None      # the real walk
    assert reader.read({**run, "facts": {}}) is None
    if metric == "lm_account_closure":
        return
    # sums that hold none of the reader's components (SDAR's shape has no
    # dense MLP, no projection outside a kernel's scope, no remat, no glue)
    bare = {STEP + "diffusion/corrupt/threefry2x32:": 30e-6}
    if metric in ("lm_unowned_ms", "lm_unscoped_ms"):
        # a remainder of nothing is a reading: the cell's line must hold it
        assert reader.read(_run(monkeypatch, bare)) == 0.0
    else:
        assert reader.read(_run(monkeypatch, bare)) is None


@pytest.mark.parametrize("metric", NEW)
def test_reader_matches_its_manifest_entry(metric):
    manifest = common.load_manifest()
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == metric]
    reader = common.load_module("layer_metrics", metric)
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert entry["layer"] == "step, model"
    assert entry["source"] == "device_trace"
    assert entry["unit"] == ("%" if metric == "lm_account_closure" else "ms")
    assert set(entry["workloads"]) <= set(LM_CELLS)
    if metric in ("lm_attn_proj_ms", "lm_attn_rest_ms", "lm_mlp_ms",
                  "lm_remat_ms"):     # where the compiled step has such ops
        assert 0 < len(entry["workloads"]) < len(LM_CELLS)
    else:
        assert entry["workloads"] == LM_CELLS
    # appended after what was there
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index(metric) > names.index("global_flash_bwd_roofline")


def test_remat_cells_are_the_configurations_that_rematerialise():
    manifest = common.load_manifest()
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "lm_remat_ms"]
    remat = [c for c in LM_CELLS
             if common.resolve_cell(c)["config"].get("remat")]
    assert entry["workloads"] == remat


# -- on the recorded trace ----------------------------------------------------

RECORDED = os.path.join(common.HERE, "testdata", "tpu_v5e_4steps.xplane.pb")


def test_the_account_closes_on_the_recorded_v5e_trace(monkeypatch):
    """A dense LM's four steps, traced before any scope existed below
    ``jit(step)``: every op is ``unowned`` or ``unscoped``, and the buckets
    still sum to the device's busy time as ``trace_reduce`` sees it."""
    from benchmark import trace_reduce

    trace = scope_times.load(RECORDED)
    window = trace_reduce.traced_window(trace, scope_times.WINDOW_SPAN)
    sums = scope_times.scope_seconds(trace, window)
    account = step_account.account(sums)
    assert sum(account.values()) == pytest.approx(sum(sums.values()))
    assert account["unowned"] > 0 and account["unscoped"] > 0
    assert sum(v for k, v in account.items()
               if k not in ("unowned", "unscoped")) == 0
    busy = trace_reduce.busy(trace, window)["busy_s"]
    monkeypatch.setattr(common, "find_xplane", lambda trace_dir: RECORDED)
    run = {"cell": {"workload": LM_CELLS[0]}, "trace": {"busy_s": busy},
           "facts": {"traced_steps": 4}}
    closure = common.load_module("layer_metrics", "lm_account_closure")
    assert closure.read(run) == pytest.approx(100.0, abs=0.5)
    unowned = common.load_module("layer_metrics", "lm_unowned_ms")
    unscoped = common.load_module("layer_metrics", "lm_unscoped_ms")
    assert (unowned.read(run) + unscoped.read(run)) * 4 == pytest.approx(
        1e3 * sum(sums.values()))
    assert common.load_module("layer_metrics",
                              "lm_remat_ms").read(run) is None


def test_the_cli_prints_the_account_and_the_longest_remainder_ops(capsys):
    step_account.main([RECORDED])
    out = capsys.readouterr().out
    for name in step_account.NAMES:
        assert f"\n{name}" in out
    assert "traced steps: 4" in out
    assert "-- longest unowned ops" in out and "-- longest unscoped ops" in out
    assert "jit(step)/pallas_call" in out
    with pytest.raises(SystemExit):
        step_account.main([])
    with pytest.raises(SystemExit):
        step_account.main([os.path.dirname(RECORDED)])
