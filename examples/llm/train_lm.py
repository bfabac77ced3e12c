"""Long-context LM training — the parallelism-suite showcase.

No reference counterpart (the reference's model zoo stops at CNNs /
wide-and-deep; SURVEY.md §5.7): this example exists because long-context and
model parallelism are first-class in the TPU build.  A decoder-only
transformer trains over a mesh combining data (dp), tensor (tp, Megatron
layouts) and sequence (sp, ring attention over ICI neighbours) parallelism;
on TPU the attention runs the Pallas flash kernel when sp=1.

Runs standalone on whatever devices are visible:

  # 8 virtual CPU devices, ring attention over sp=2:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python train_lm.py --tp 2 --sp 2 --seq-len 512 --steps 10

  # 1F1B pipeline over pp=2 stages, dp over the remaining devices:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python train_lm.py --pp 2 --batch 16 --seq-len 512 --steps 10

  # single real TPU chip, Pallas flash attention:
  python train_lm.py --seq-len 2048 --steps 20
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def synthetic_ids(batch, seq_len, vocab, seed=0):
    """Zipf-ish token stream: enough structure for the loss to move."""
    import numpy as np

    rng = np.random.RandomState(seed)
    base = rng.zipf(1.5, size=(batch, seq_len)).astype("int64")
    return (base % vocab).astype("int32")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--vocab-size", type=int, default=4096)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--n-layers", type=int, default=4)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--n-experts", type=int, default=0, help=">0 enables MoE over ep")
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--ep", type=int, default=1)
    p.add_argument("--pp", type=int, default=1,
                   help=">1 trains the blocks as a 1F1B pipeline over pp "
                        "stages (embed + loss head outside the pipe, "
                        "O(stages) activation memory); requires tp=sp=ep=1")
    p.add_argument("--pp-microbatches", type=int, default=4)
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--vocab-chunk", type=int, default=0,
                   help=">0 fuses the lm_head into a blockwise cross-entropy "
                        "(ops/xent.py) — never materializes [B,S,V] logits; "
                        "use with tp=1")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize per-block activations (jax.checkpoint)"
                        " — O(1) residuals per block for ~1/3 extra FLOPs")
    p.add_argument("--accum-steps", type=int, default=1,
                   help=">1 splits each batch into microbatches and "
                        "accumulates gradients before the optimizer update")
    p.add_argument("--generate", type=int, default=0,
                   help=">0 greedily decodes this many tokens after training "
                        "(KV-cache serving loop)")
    p.add_argument("--profile-dir", default="",
                   help="write a jax profiler trace of the steady state here")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu import profiling
    from tensorflowonspark_tpu.models import transformer as tfm
    from tensorflowonspark_tpu.parallel import dp as dplib
    from tensorflowonspark_tpu.parallel import mesh as meshlib
    from tensorflowonspark_tpu.parallel import tp as tplib

    if args.pp > 1:
        _train_pipelined(args)
        return

    mesh = meshlib.make_mesh(dp=-1, tp=args.tp, sp=args.sp, ep=args.ep)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"on {jax.default_backend()}")

    attn_impl = "ring" if args.sp > 1 else "auto"
    model = tfm.Transformer(
        vocab_size=args.vocab_size, d_model=args.d_model,
        n_layers=args.n_layers, n_heads=args.n_heads,
        n_experts=args.n_experts, attn_impl=attn_impl, mesh=mesh,
        compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        remat=args.remat)

    ids = jnp.asarray(synthetic_ids(args.batch, args.seq_len, args.vocab_size))
    # init traces the model too, so the init batch must satisfy the same
    # mesh divisibility as training batches (the ring-attention shard_map).
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {n_params/1e6:.2f}M params, attn={attn_impl}")

    optimizer = optax.adamw(args.lr)
    with jax.set_mesh(mesh):
        shardings = tplib.rule_shardings(mesh, params,
                                         tplib.TRANSFORMER_TP_RULES)
        shardings = tplib.compose_fsdp(mesh, params, shardings)
        params = meshlib.shard_tree(mesh, params, shardings)
        state = dplib.TrainState.create(params, optimizer)
        step = dplib.make_train_step(
            tfm.make_loss_fn(model, vocab_chunk=args.vocab_chunk), optimizer,
            accum_steps=args.accum_steps)
        batch = meshlib.shard_batch(mesh, {"input_ids": np.asarray(ids)})

        state, metrics = step(state, batch)  # compile
        print(f"step 0: loss={float(metrics['loss']):.4f}")

        def one_step():
            nonlocal state
            state, m = step(state, batch)
            return m

        t0 = time.perf_counter()
        if args.profile_dir:
            # warmup already happened (the compile step above), so the timed
            # window covers exactly args.steps executions.
            metrics = profiling.profile_steps(args.profile_dir, one_step,
                                              warmup=0, steps=args.steps)
        else:
            for _ in range(args.steps):
                metrics = one_step()
        loss = float(metrics["loss"])  # fetch = sync
        dt = time.perf_counter() - t0

        tokens = args.batch * args.seq_len * args.steps
        print(f"step {args.steps}: loss={loss:.4f} "
              f"({tokens / dt:,.0f} tokens/sec)")
        params_host = jax.device_get(state.params) if args.generate else None

    if args.generate:
        # Outside the mesh context: decode is a batch-1 single-device loop,
        # and the model's activation-sharding hints no-op without a mesh.
        # Re-place the host snapshot once: handing numpy params to the jitted
        # decode step would re-transfer the full weight tree host->device on
        # EVERY generated token.
        decode_params = jax.device_put(params_host, jax.devices()[0])
        prompt = np.asarray(ids[:1, :8])
        out = tfm.greedy_generate(model.clone(mesh=None, attn_impl="xla"),
                                  decode_params, jnp.asarray(prompt),
                                  max_new_tokens=args.generate)
        print(f"generated: {out[0].tolist()}")


def _train_pipelined(args) -> None:
    """1F1B pipeline-parallel LM training (--pp N).

    Blocks are the pipeline stages (``n_layers / pp`` per stage); the
    embedding and the loss head (final norm + lm_head + shifted
    cross-entropy) live outside the pipe and train through
    ``pipeline_1f1b``'s ``head_params`` / ``with_input_grad`` paths — every
    parameter gets the sequential gradient (tests/test_parallel_pp.py).
    """
    import jax
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu.models import transformer as tfm
    from tensorflowonspark_tpu.parallel import mesh as meshlib
    from tensorflowonspark_tpu.parallel import pp as pplib

    if args.tp != 1 or args.sp != 1 or args.ep != 1 or args.n_experts:
        raise SystemExit("--pp composes with dp only; set tp=sp=ep=1, "
                         "n_experts=0")
    if args.generate or args.accum_steps != 1:
        raise SystemExit("--pp does not support --generate/--accum-steps "
                         "(decode uses the non-pp path; 1F1B already "
                         "microbatches every step)")
    if args.remat:
        raise SystemExit("--remat is implicit under --pp: 1F1B saves only "
                         "stage inputs and recomputes stage forwards")
    if args.n_layers % args.pp:
        raise SystemExit(f"--n-layers {args.n_layers} not divisible by "
                         f"--pp {args.pp}")
    if len(jax.devices()) < args.pp:
        raise SystemExit(f"--pp {args.pp} needs {args.pp} devices, have "
                         f"{len(jax.devices())}")

    # dp over whatever devices remain: each dp row runs its own pipeline on
    # its batch shard, grads averaged (pipeline_1f1b's data_axis path).
    mesh = meshlib.make_mesh(dp=-1, pp=args.pp)
    dp_size = mesh.shape["dp"]
    m = args.pp_microbatches
    if args.batch % (dp_size * m):
        raise SystemExit(f"--batch {args.batch} not divisible by dp x "
                         f"--pp-microbatches = {dp_size} x {m}")
    per_stage = args.n_layers // args.pp
    bubble = (args.pp - 1) / (m + args.pp - 1)
    print(f"mesh: dp={dp_size} pp={args.pp} on {jax.default_backend()}; "
          f"{per_stage} blocks/stage, {m} microbatches/row, "
          f"bubble {bubble:.0%}")

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    model = tfm.Transformer(
        vocab_size=args.vocab_size, d_model=args.d_model,
        n_layers=args.n_layers, n_heads=args.n_heads, attn_impl="xla",
        compute_dtype=dtype)
    ids = jnp.asarray(synthetic_ids(args.batch, args.seq_len,
                                    args.vocab_size))
    full = model.init(jax.random.PRNGKey(0), ids)["params"]
    n_params = sum(x.size for x in jax.tree.leaves(full))
    print(f"model: {n_params/1e6:.2f}M params, 1F1B pipeline")

    block = tfm.Block(n_heads=args.n_heads,
                      d_head=args.d_model // args.n_heads,
                      d_ff=4 * args.d_model, attn_impl="xla",
                      compute_dtype=dtype)

    def stage_tree(i):
        return jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *(full[f"block_{i * per_stage + j}"] for j in range(per_stage)))

    stacked = pplib.stack_stages([stage_tree(i) for i in range(args.pp)])
    stacked = jax.device_put(stacked, pplib.stage_shardings(mesh, stacked))
    head = {"final_norm": full["final_norm"], "lm_head": full["lm_head"]}
    emb = full["embed"]

    def stage_fn(p, h):
        for j in range(per_stage):
            h = block.apply({"params": jax.tree.map(lambda a: a[j], p)}, h)
        return h

    import flax.linen as nn

    from tensorflowonspark_tpu.ops import xent

    def head_loss(hp, h, tgt_ids):
        final = tfm.RMSNorm().apply({"params": hp["final_norm"]}, h)
        tgt = tgt_ids[:, 1:]
        if args.vocab_chunk:
            # fused blockwise head: never materializes [mb, S, V] logits
            return xent.blockwise_cross_entropy(
                final[:, :-1].reshape(-1, args.d_model),
                hp["lm_head"]["kernel"], tgt.reshape(-1),
                chunk=args.vocab_chunk) / tgt.size
        logits = nn.Dense(args.vocab_size, use_bias=False, dtype=dtype).apply(
            {"params": hp["lm_head"]}, final).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits[:, :-1])
        return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], axis=-1))

    optimizer = optax.adamw(args.lr)
    params = (stacked, head, emb)
    opt_state = optimizer.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def pp_step(params, opt_state, ids):
        stacked, head, emb = params
        x = emb["embedding"][ids].astype(dtype)
        loss, g_s, g_h, dx = pplib.pipeline_1f1b(
            stage_fn, stacked, x, head_loss, mesh=mesh, n_microbatches=m,
            targets=ids, head_params=head, with_input_grad=True)
        g_e = {"embedding": jax.grad(
            lambda e: jnp.sum(e[ids].astype(jnp.float32) * dx))(
                emb["embedding"])}
        updates, opt_state = optimizer.update((g_s, g_h, g_e), opt_state,
                                              params)
        return optax.apply_updates(params, updates), opt_state, loss

    params, opt_state, loss = pp_step(params, opt_state, ids)  # compile
    print(f"step 0: loss={float(loss):.4f}")

    def one_step():
        nonlocal params, opt_state
        params, opt_state, loss = pp_step(params, opt_state, ids)
        return loss

    t0 = time.perf_counter()
    if args.profile_dir:
        from tensorflowonspark_tpu import profiling

        loss = profiling.profile_steps(args.profile_dir, one_step,
                                       warmup=0, steps=args.steps)
    else:
        for _ in range(args.steps):
            loss = one_step()
    loss = float(loss)  # fetch = sync
    dt = time.perf_counter() - t0
    tokens = args.batch * args.seq_len * args.steps
    print(f"step {args.steps}: loss={loss:.4f} "
          f"({tokens / dt:,.0f} tokens/sec)")


if __name__ == "__main__":
    main()
