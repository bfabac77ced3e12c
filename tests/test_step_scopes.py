"""The scopes the step's account is built on (ISSUE 50): each LM
configuration's ``make_train_step``, lowered at the rehearsal's reduced
sizes with the kernels traced (``pallas_interpret``), names its work so that
``benchmark/step_account.py`` finds an owner for it.

- ``lm_head_loss`` is a WHOLE component of forward and of backward
  instructions, in the fused and in the plain loss, with and without the
  multi-token-prediction module (``_loss_scope``);
- no instruction that carries metadata lands in the account's ``unowned``
  bucket, but for the few this file lists;
- every scope an accepted reader sums by holds as many instructions as it
  held at the parent commit (``step_scope_counts_parent.json``, recorded from
  the parent's lowering before the first edit): a scope was added around
  what had none, nothing was renamed, moved or nested away from a reader.

Metadata only: nothing here runs a step."""

from __future__ import annotations

import collections
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import common, scope_times, step_account
from benchmark import run as bench_run
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.parallel import dp

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = [   # one cell of each LM configuration
    "phi3_mini_d4_train_2k", "olmoe_1b_7b_d1_train_4k",
    "sdar_30b_a3b_d4_ep8_train_bd4k", "keye_vl2_30b_a3b_d4_ep8_train_16k",
    "kanana2_30b_a3b_d5_ep8_train_8k",
    "nemotron3_super_d11_tp8_ep64_train_8k",
    "xing4_29b_a4b_d5_tp8_ep8_train_4k",
    "smallthinker_21b_a3b_d8_ep8_train_16k"]
# the scopes the accepted readers of benchmark/layer_metrics sum by
READ = ["optimizer_update", "flash_fwd", "flash_bwd", "flash_fwd_window",
        "flash_bwd_window", "dsa/index", "dsa/select", "dsa/attend",
        "dsa/index_loss", "mla/project", "moe/router", "moe/dispatch",
        "moe/combine", "moe/experts", "moe/shared", "moe/latent", "ssm",
        "ssm/scan", "hc/maps", "hc/pre", "hc/post", "mtp",
        "diffusion/corrupt"]
# What may stay unowned, by its path after ``jit(step)/``: ``state.step + 1``
# (``make_train_step``; inside ``optimizer_update`` it would be adamw's), the
# ops that the two wrapped losses trace directly under their wrapper or past
# it (scalars: the index loss's mean, ``masked_share``, the total), and
# ``jax.checkpoint``'s own plumbing of a rematerialised block's residuals.
UNOWNED_OK = re.compile(
    r"^(add"
    r"|loss_and_grad/(transpose\()?jvp\((sparse_lm|block_diffusion|)\)+/[^/]*"
    r"|.*/remat2)$")


# -- a lowered step's instructions, named as the chip's compiler names them ---

_COMPUTATION = re.compile(r"^(ENTRY )?%?(\S+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?\S+ = .*? ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(r"to_apply=%?([\w.\-]+)")
_BODIES = re.compile(r"(?:body|condition)=%?([\w.\-]+)"
                     r"|branch_computations=\{([^}]*)\}")


def _hlo_text(lowered) -> str:
    from jax._src.lib import xla_client

    options = xla_client._xla.HloPrintOptions()
    options.print_metadata = True
    return lowered.compiler_ir(dialect="hlo").as_hlo_module().to_string(
        options)


def op_names(lowered) -> list[str]:
    """The ``op_name`` of every instruction of the lowered step that carries
    one, as the TPU compiler leaves it.  A jitted helper, a ``custom_vjp``
    rule or a checkpointed block is a CALLED computation whose instructions
    are named from the callee's own root (``while/body/mul``); XLA's call
    inliner puts the call's ``op_name`` before them (read off a step compiled
    for a described v5e: ``.../flash_bwd/jit(_flash_bwd_pallas)/
    pallas_call``).  So does this: a callee's instruction is listed once for
    each call site, under that site's path."""
    computations: dict[str, list] = {}
    entry = current = None
    for line in _hlo_text(lowered).splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = head.group(2)
            computations[current] = []
            entry = current if head.group(1) else entry
        elif line.startswith("}"):
            current = None
        elif current and (instruction := _INSTRUCTION.match(line)):
            opcode = instruction.group(1)
            if opcode == "parameter":       # named after the argument
                continue
            name = _OP_NAME.search(line)
            callee = _CALLEE.search(line) if opcode == "call" else None
            bodies = [b.strip().lstrip("%") for found in _BODIES.findall(line)
                      for part in found for b in part.split(",") if b.strip()]
            computations[current].append(
                (name.group(1) if name else "",
                 callee.group(1) if callee else None, bodies))
    out: list[str] = []
    todo = [(entry, "")]
    while todo:
        computation, prefix = todo.pop()
        for name, callee, bodies in computations[computation]:
            path = f"{prefix}/{name}" if prefix and name else prefix or name
            if name:
                out.append(path)
            if callee:
                todo.append((callee, path))
            # a loop's or a branch's body is named from the same root
            todo += [(body, prefix) for body in bodies]
    return out


def _cell_step(workload: str):
    """``(the cell's jitted step, its state, one batch, the mesh)`` at the
    rehearsal's sizes, the attention kernels traced."""
    from tensorflowonspark_tpu.parallel.mesh import make_mesh

    cell = common.resolve_cell(workload)
    bench_run.apply_rehearsal(cell)
    cfg, traffic = cell["config"], cell["traffic"]
    cfg["attn_impl"] = "pallas_interpret"
    config = common.load_module("configs", cell["config_name"], cell["base"])
    mesh = make_mesh(devices=jax.devices()[:1], dp=-1)
    built = config.build_train(cfg, traffic, mesh, 1)
    rows = list(config.train_records(cfg, traffic, np.random.default_rng(0),
                                     built["rows_per_step"]))
    return (built["step_fn"], built["state"],
            config.rows_to_arrays(cfg)(rows), mesh)


@pytest.fixture(scope="module")
def lowered_names():
    """``workload -> op_names`` of its lowered step, lowered once."""
    cache: dict[str, list[str]] = {}

    def names(workload: str) -> list[str]:
        if workload not in cache:
            step, state, batch, mesh = _cell_step(workload)
            with jax.set_mesh(mesh):
                cache[workload] = op_names(step.lower(state, batch))
        return cache[workload]

    return names


# -- every configuration's step -----------------------------------------------

@pytest.mark.parametrize("workload", CELLS)
def test_head_and_loss_are_a_whole_component_in_both_halves(lowered_names,
                                                            workload):
    names = lowered_names(workload)
    head = [n for n in names if scope_times.in_scope(n, "lm_head_loss")]
    assert [n for n in head if "/jvp(" in n and "transpose(" not in n]
    assert [n for n in head if "transpose(" in n]
    # no transform names itself around the scope any more
    assert not [n for n in names if "(lm_head_loss)" in n]
    assert not [n for n in names if "(loss_terms)" in n]
    if workload.startswith("xing4_"):       # the MTP module's second pass
        mtp_head = [n for n in head if "/mtp/lm_head_loss/" in n]
        assert [n for n in mtp_head if "transpose(" in n]
        assert [n for n in mtp_head if "transpose(" not in n]
        assert len(mtp_head) < len(head)


@pytest.mark.parametrize("workload", CELLS)
def test_no_named_instruction_is_unowned_but_the_listed(lowered_names,
                                                        workload):
    unowned = collections.Counter(
        re.sub(r"^jit\(step\)/(jit\(main\)/)?", "", n)
        for n in lowered_names(workload)
        if step_account.bucket_of(n) == step_account.UNOWNED)
    surprises = {n: c for n, c in unowned.items() if not UNOWNED_OK.match(n)}
    assert not surprises, sorted(surprises.items())[:20]
    # and the listed are few: scalars and plumbing, no layer's work
    assert sum(unowned.values()) < 0.02 * len(lowered_names(workload))


@pytest.mark.parametrize("workload", CELLS)
def test_each_read_scope_holds_the_parent_s_instructions(lowered_names,
                                                         workload):
    with open(os.path.join(HERE, "step_scope_counts_parent.json")) as f:
        parent = json.load(f)[workload]
    names = lowered_names(workload)
    counts = {scope: sum(1 for n in names if scope_times.in_scope(n, scope))
              for scope in READ}
    assert {s: c for s, c in counts.items() if c} == parent
    assert len(parent) >= 3                 # the table is not empty


@pytest.mark.parametrize("workload", CELLS)
def test_the_new_scopes_are_whole_components_where_the_model_has_them(
        lowered_names, workload):
    names = lowered_names(workload)
    residual = [n for n in names if scope_times.in_scope(n, "residual")]
    assert [n for n in residual if "transpose(" in n]
    assert [n for n in residual if "transpose(" not in n]
    terms = [n for n in names if scope_times.in_scope(n, "loss_terms")]
    # a model without experts sows nothing: its terms are constants
    assert bool(terms) == (not workload.startswith("phi3_"))
    for n in residual + terms:
        assert step_account.bucket_of(n) == "norms and glue", n


# -- the four forms of make_loss_fn -------------------------------------------

def _tiny_step_names(vocab_chunk: int, mtp: bool) -> list[str]:
    extra = ({"num_nextn_predict_layers": 1, "n_experts": 4, "moe_top_k": 2,
              "moe_capacity_factor": None} if mtp else {})
    model = tfm.build_transformer({
        "vocab_size": 64, "d_model": 32, "n_layers": 1, "n_heads": 2,
        "d_ff": 64, "attn_impl": "xla", **extra})
    ids = jnp.zeros((2, 16), jnp.int32)
    variables = jax.eval_shape(lambda: model.init(jax.random.key(0), ids))
    optimizer = optax.adamw(1e-3)
    state = jax.eval_shape(
        lambda p, b: dp.TrainState.create(p, optimizer, b),
        variables["params"], variables.get("buffers"))
    step = dp.make_train_step(
        tfm.make_loss_fn(model, vocab_chunk=vocab_chunk), optimizer)
    return op_names(step.lower(state, {"input_ids": ids}))


@pytest.mark.parametrize("mtp", [False, True], ids=["trunk", "mtp"])
@pytest.mark.parametrize("vocab_chunk", [0, 32], ids=["plain", "fused"])
def test_make_loss_fn_names_the_head_whole_forward_and_backward(vocab_chunk,
                                                                mtp):
    names = _tiny_step_names(vocab_chunk, mtp)
    # jvp(lm_loss)/lm_head_loss/..., transpose(jvp(lm_loss))/lm_head_loss/...
    trunk = [n for n in names if re.search(r"\(lm_loss\)+/lm_head_loss/", n)]
    assert [n for n in trunk if "transpose(" in n], "backward"
    assert [n for n in trunk if "transpose(" not in n], "forward"
    # a dense model's auxiliary terms are constants: nothing is traced there
    terms = [n for n in names if re.search(r"\(lm_loss\)+/loss_terms/", n)]
    assert bool(terms) == mtp
    second = [n for n in names
              if re.search(r"\(mtp_loss\)+/mtp/lm_head_loss/", n)]
    assert bool(second) == mtp
    if mtp:
        assert [n for n in second if "transpose(" in n]
        assert [n for n in names
                if re.search(r"\(mtp_loss\)+/loss_terms/", n)]
    assert not [n for n in names if "(lm_head_loss)" in n or "jvp()" in n]
    for n in trunk + second:
        assert step_account.bucket_of(n) == "head"
