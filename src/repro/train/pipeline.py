"""GPipe pipeline parallelism over a mesh axis (usually the DCN 'pod' axis).

Layers are stacked [L, ...] and viewed as [n_stages, L/n_stages, ...] with
dim0 sharded over the stage axis via shard_map; activations hand off between
stages with ``lax.ppermute`` inside a ``lax.scan`` over the GPipe schedule
(T = n_micro + n_stages - 1 ticks, bubble fraction (S-1)/T).  ``jax.grad``
differentiates straight through (ppermute's transpose is the reverse
permute), so the 1F1B-style backward falls out of autodiff.

Supports 'uniform'-pattern decoder configs (every assigned dense arch).  The
embedding/head run on every stage replica but only their own tick's data is
used — simple, and the matmuls are negligible next to the stack.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import layers as L, transformer
from repro.models.config import ModelConfig
from repro.train.step import chunked_ce


def stage_view(params: dict, n_stages: int) -> dict:
    """Reshape stacked layer weights [L, ...] -> [n_stages, L/S, ...]."""
    out = dict(params)
    out["layers"] = jax.tree.map(
        lambda a: a.reshape((n_stages, a.shape[0] // n_stages) + a.shape[1:]),
        params["layers"],
    )
    return out


def pipeline_loss_fn(
    cfg: ModelConfig,
    mesh: Mesh,
    n_micro: int,
    staged_example,
    stage_axis: str = "pod",
    batch_axes: tuple = ("data",),
):
    """Returns loss(params_staged, tokens, labels) with pipeline execution.

    params_staged: model params with ['layers'] leaves shaped
    [n_stages, L/S, ...] (dim0 sharded over ``stage_axis``); other params
    replicated. ``staged_example``: any pytree with that structure (used to
    build per-leaf shard_map specs).  tokens/labels: [B, S] over batch_axes.
    """
    n_stages = mesh.shape[stage_axis]
    plans = transformer.group_plans(cfg)
    assert len(plans) == 1 and plans[0].name == "layers", (
        "pipeline parallelism supports uniform decoder stacks"
    )
    plan = plans[0]
    pspec = jax.tree.map(lambda _: P(), staged_example)
    pspec["layers"] = jax.tree.map(lambda _: P(stage_axis), staged_example["layers"])

    def stack_fwd(layer_params, x, positions):
        def body(carry, lp):
            h = carry
            for i, (mixer, ffn) in enumerate(plan.sublayers):
                window = cfg.sliding_window if mixer == "attn" else 0
                h, _ = transformer._layer_fwd(
                    lp[f"s{i}"], cfg, h, positions, mixer, ffn, window=window
                )
            return h, None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, layer_params)
        return x

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(pspec, P(batch_axes, None), P(batch_axes, None)),
        out_specs=P(),
        check_vma=False,
    )
    def run(staged_params, tokens, labels):
        stage = jax.lax.axis_index(stage_axis)
        local_layers = jax.tree.map(lambda a: a[0], staged_params["layers"])
        b, s = tokens.shape
        assert b % n_micro == 0, (b, n_micro)
        mb = b // n_micro
        positions = jnp.arange(s, dtype=jnp.int32)
        micros_t = tokens.reshape(n_micro, mb, s)
        micros_l = labels.reshape(n_micro, mb, s)
        embed = staged_params["embed"].astype(jnp.bfloat16)
        head = (
            staged_params["embed"].T
            if cfg.tie_embeddings
            else staged_params["lm_head"]
        ).astype(jnp.bfloat16)

        n_ticks = n_micro + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            x_state, loss_sum, cnt_sum = carry
            # stage 0 ingests microbatch t (or zeros past the end)
            mt = micros_t[jnp.minimum(t, n_micro - 1)]
            x_in0 = embed[mt]
            x_in = jnp.where(stage == 0, x_in0, x_state)
            y = stack_fwd(local_layers, x_in, positions)
            # last stage: loss for microbatch (t - (n_stages-1))
            mi = t - (n_stages - 1)
            lab = micros_l[jnp.clip(mi, 0, n_micro - 1)]
            h = transformer.layers.norm_fwd(staged_params["final_norm"], cfg, y)
            lsum, lcnt = _masked_ce(h, head, lab)
            take = (stage == n_stages - 1) & (mi >= 0)
            loss_sum = loss_sum + jnp.where(take, lsum, 0.0)
            cnt_sum = cnt_sum + jnp.where(take, lcnt, 0.0)
            # hand off activations to the next stage
            x_next = jax.lax.ppermute(y, stage_axis, perm)
            return (x_next, loss_sum, cnt_sum), None

        x0 = jnp.zeros((mb, s, cfg.d_model), jnp.bfloat16)
        (xf, loss_sum, cnt_sum), _ = jax.lax.scan(
            tick, (x0, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            jnp.arange(n_ticks),
        )
        # total over stages (only last stage contributed) and batch shards
        loss_sum = jax.lax.psum(loss_sum, stage_axis)
        cnt_sum = jax.lax.psum(cnt_sum, stage_axis)
        if batch_axes:
            loss_sum = jax.lax.psum(loss_sum, batch_axes)
            cnt_sum = jax.lax.psum(cnt_sum, batch_axes)
        return loss_sum / jnp.maximum(cnt_sum, 1.0)

    def _masked_ce(h, head, labels):
        logits = (h @ head).astype(jnp.float32)
        valid = labels >= 0
        safe = jnp.maximum(labels, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        ce = jnp.where(valid, lse - gold, 0.0)
        return jnp.sum(ce), jnp.sum(valid).astype(jnp.float32)

    return run
