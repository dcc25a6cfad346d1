"""Model assembly: spec trees, scan-over-layers forward passes, KV caches.

Layer stacks are grouped into *scan groups* of structurally identical blocks
(weights stacked on a leading 'layers' axis, iterated with ``lax.scan``) —
keeps HLO size O(1) in depth, the standard MaxText approach:

  uniform   — n identical decoder layers (attn|mla|ssm mixer + mlp|moe ffn)
  deepseek  — 3 dense layers, then 58 MoE layers (two scan groups) + MTP
  jamba     — 4 blocks × [7 mamba + 1 attn sublayers, alternating mlp/moe]
  vlm       — 8 blocks × [4 self-attn + 1 cross-attn layers]
  encdec    — whisper: bidirectional encoder scan + causal decoder scan with
              cross-attention (frame embeddings from the stubbed frontend)

Every forward returns (logits, aux) where aux carries MoE load-balancing
losses; serve paths return/consume cache pytrees whose leading dim mirrors
the scan group stacking.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import layers, mla, moe, ssm
from repro.models.config import ModelConfig
from repro.models.params import ParamSpec


# ---------------------------------------------------------------------------
# spec utilities
# ---------------------------------------------------------------------------

def stack_specs(tree, n: int):
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.scale),
        tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def _layer_specs(cfg: ModelConfig, mixer: str, ffn: str) -> dict:
    if mixer == "attn":
        mix = layers.attention_specs(cfg)
    elif mixer == "cross":
        mix = layers.attention_specs(cfg, cross=True)
    elif mixer == "mla":
        mix = mla.mla_specs(cfg)
    elif mixer == "ssm":
        mix = ssm.ssm_specs(cfg)
    else:
        raise ValueError(mixer)
    out = {"mixer_norm": layers.norm_specs(cfg), "mixer": mix}
    if ffn == "mlp":
        out["ffn_norm"] = layers.norm_specs(cfg)
        out["ffn"] = layers.mlp_specs(cfg)
    elif ffn == "moe":
        out["ffn_norm"] = layers.norm_specs(cfg)
        out["ffn"] = moe.moe_specs(cfg)
    elif ffn == "none":
        pass
    else:
        raise ValueError(ffn)
    return out


def _layer_fwd(p, cfg, x, positions, mixer, ffn, *, window=0, enc_out=None,
               enc_positions=None):
    """Residual decoder layer, full-sequence. Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = layers.norm_fwd(p["mixer_norm"], cfg, x)
    if mixer == "attn":
        h = layers.attention_fwd(p["mixer"], cfg, h, positions, causal=True,
                                 window=window)
    elif mixer == "cross":
        h = layers.attention_fwd(p["mixer"], cfg, h, positions, causal=False,
                                 kv_x=enc_out, kv_positions=enc_positions)
    elif mixer == "enc_attn":
        h = layers.attention_fwd(p["mixer"], cfg, h, positions, causal=False)
    elif mixer == "mla":
        h = mla.mla_fwd(p["mixer"], cfg, h, positions)
    elif mixer == "ssm":
        h, _ = ssm.ssm_fwd(p["mixer"], cfg, h)
    x = x + h
    if ffn != "none":
        h = layers.norm_fwd(p["ffn_norm"], cfg, x)
        if ffn == "moe":
            h, a = moe.moe_fwd(p["ffn"], cfg, h)
            aux = aux + a
        else:
            h = layers.mlp_fwd(p["ffn"], cfg, h)
        x = x + h
    return x, aux


def _layer_decode(p, cfg, x, cache, mixer, ffn, *, window=0):
    """Residual decoder layer, one token, with cache. Returns (x, cache)."""
    h = layers.norm_fwd(p["mixer_norm"], cfg, x)
    if mixer == "attn":
        h, cache = layers.attention_decode(p["mixer"], cfg, h, cache, window=window)
    elif mixer == "cross":
        # cross K/V cached at prefill; attend with no causal mask
        q, _, _ = layers._project_qkv(p["mixer"], cfg, h)
        kk = layers.repeat_kv(cache["k"], cfg.n_heads)
        vv = layers.repeat_kv(cache["v"], cfg.n_heads)
        import numpy as np

        sc = jnp.einsum("bshd,bthd->bhst", q, kk).astype(jnp.float32)
        sc = sc / np.sqrt(q.shape[-1])
        pr = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
        o = jnp.einsum("bhst,bthd->bshd", pr, vv)
        h = jnp.einsum("bshd,hdo->bso", o, p["mixer"]["wo"].astype(x.dtype))
    elif mixer == "mla":
        h, cache = mla.mla_decode(p["mixer"], cfg, h, cache, absorb=cfg.mla_absorb)
    elif mixer == "ssm":
        h, cache = ssm.ssm_decode(p["mixer"], cfg, h, cache)
    x = x + h
    if ffn != "none":
        h = layers.norm_fwd(p["ffn_norm"], cfg, x)
        if ffn == "moe":
            h, _ = moe.moe_fwd(p["ffn"], cfg, h)
        else:
            h = layers.mlp_fwd(p["ffn"], cfg, h)
        x = x + h
    return x, cache


def _layer_cache(cfg, mixer, batch, max_seq, window=0, enc_len=0, dtype=jnp.bfloat16):
    if mixer == "attn":
        return layers.init_attn_cache(cfg, batch, max_seq, window, dtype)
    if mixer == "cross":
        return {
            "k": jnp.zeros((batch, enc_len, cfg.n_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, enc_len, cfg.n_kv_heads, cfg.head_dim), dtype),
        }
    if mixer == "mla":
        return mla.init_mla_cache(cfg, batch, max_seq, dtype)
    if mixer == "ssm":
        return ssm.init_ssm_state(cfg, batch, dtype)
    raise ValueError(mixer)


# ---------------------------------------------------------------------------
# group plans: which scan groups a config lowers to
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GroupPlan:
    name: str
    n: int  # scan length (number of stacked blocks)
    sublayers: tuple[tuple[str, str], ...]  # (mixer, ffn) per sublayer in a block


def group_plans(cfg: ModelConfig) -> list[GroupPlan]:
    if cfg.encoder is not None:  # whisper: decoder here; encoder handled apart
        return [GroupPlan("dec", cfg.n_layers, (("attn", "none"), ("cross", "mlp")))]
    if cfg.vision is not None:
        k = cfg.vision.cross_attn_every
        assert cfg.n_layers % k == 0
        subs = tuple([("attn", "mlp")] * (k - 1) + [("cross", "mlp")])
        return [GroupPlan("blocks", cfg.n_layers // k, subs)]
    if cfg.layer_pattern == "jamba":
        per = cfg.attn_every
        assert cfg.n_layers % per == 0
        subs = []
        for i in range(per):
            mixer = "attn" if i == per // 2 else "ssm"
            ffn = "moe" if (cfg.moe is not None and i % cfg.moe.every == cfg.moe.every - 1) else "mlp"
            subs.append((mixer, ffn))
        return [GroupPlan("blocks", cfg.n_layers // per, tuple(subs))]
    if cfg.ssm is not None:  # pure SSM
        return [GroupPlan("layers", cfg.n_layers, (("ssm", "none"),))]
    mixer = "mla" if cfg.mla is not None else "attn"
    if cfg.moe is not None:
        fd = cfg.moe.first_dense
        plans = []
        if fd:
            plans.append(GroupPlan("dense", fd, ((mixer, "mlp"),)))
        if cfg.moe.every > 1:
            subs = tuple(
                (mixer, "moe" if i % cfg.moe.every == cfg.moe.every - 1 else "mlp")
                for i in range(cfg.moe.every)
            )
            plans.append(GroupPlan("moe", (cfg.n_layers - fd) // cfg.moe.every, subs))
        else:
            plans.append(GroupPlan("moe", cfg.n_layers - fd, ((mixer, "moe"),)))
        return plans
    return [GroupPlan("layers", cfg.n_layers, ((mixer, "mlp"),))]


# ---------------------------------------------------------------------------
# model specs
# ---------------------------------------------------------------------------

def model_specs(cfg: ModelConfig) -> dict:
    d, v = cfg.d_model, cfg.vocab_size
    out: dict[str, Any] = {
        "embed": ParamSpec((v, d), ("vocab", "embed"), init="embed"),
        "final_norm": layers.norm_specs(cfg),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = ParamSpec((d, v), ("embed", "vocab"))
    for plan in group_plans(cfg):
        block = {f"s{i}": _layer_specs(cfg, m, f) for i, (m, f) in enumerate(plan.sublayers)}
        out[plan.name] = stack_specs(block, plan.n)
    if cfg.encoder is not None:
        enc_block = {"s0": _layer_specs(cfg, "attn", "mlp")}
        # encoder self-attention is bidirectional; same spec shapes
        out["encoder"] = stack_specs(enc_block, cfg.encoder.n_layers)
        out["enc_final_norm"] = layers.norm_specs(cfg)
        out["enc_pos"] = ParamSpec(
            (cfg.encoder.n_frames, d), ("frames", "embed"), init="embed"
        )
    if cfg.vision is not None:
        out["vision_norm"] = layers.norm_specs(cfg)
    if cfg.mtp_depth:
        mtp_block = {
            "proj": ParamSpec((2 * d, d), ("embed", None)),
            "norm": layers.norm_specs(cfg),
            "layer": _layer_specs(cfg, "mla" if cfg.mla else "attn", "mlp"),
        }
        out["mtp"] = mtp_block
    return out


# ---------------------------------------------------------------------------
# forward (training / scoring)
# ---------------------------------------------------------------------------

REMAT_POLICY = "full"  # 'full' | 'dots' (save matmul outputs: no re-gather
# of FSDP weights in the backward pass, more activation memory) | 'none'


def _remat_wrap(body, remat: bool):
    if not remat or REMAT_POLICY == "none":
        return body
    if REMAT_POLICY == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(body)


def _scan_group(params_group, x, positions, cfg, plan: GroupPlan, *, remat: bool,
                enc_out=None, enc_positions=None):
    def block_body(carry, layer_params):
        h, aux = carry
        # barrier: stops XLA commuting convert(dynamic-slice(stack)) into
        # dynamic-slice(convert(stack)), which would materialise an f32 copy
        # of the whole saved-activation stack (2× activation memory).
        h = jax.lax.optimization_barrier(h)
        h = layers.constrain_seq(h)
        for i, (mixer, ffn) in enumerate(plan.sublayers):
            window = cfg.sliding_window if mixer == "attn" else 0
            h, a = _layer_fwd(
                layer_params[f"s{i}"], cfg, h, positions, mixer, ffn,
                window=window, enc_out=enc_out, enc_positions=enc_positions,
            )
            aux = aux + a
            h = layers.constrain_seq(h)
        return (h, aux), None

    body = _remat_wrap(block_body, remat)
    (x, aux), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), params_group
    )
    return x, aux


def _encode(params, cfg: ModelConfig, frames, patches, dtype=jnp.bfloat16):
    """Run the (stub-fronted) encoder side: whisper frames or VLM patches.
    Returns (enc_out, enc_positions) or (None, None)."""
    if cfg.encoder is not None:
        assert frames is not None, "whisper needs frame embeddings (stub frontend)"
        e = frames.astype(dtype) + params["enc_pos"].astype(dtype)[None]
        e_pos = jnp.arange(frames.shape[1], dtype=jnp.int32)

        def enc_body(carry, lp):
            h, _ = carry
            hh = layers.norm_fwd(lp["s0"]["mixer_norm"], cfg, h)
            hh = layers.attention_fwd(lp["s0"]["mixer"], cfg, hh, e_pos, causal=False)
            h = h + hh
            hh = layers.norm_fwd(lp["s0"]["ffn_norm"], cfg, h)
            h = h + layers.mlp_fwd(lp["s0"]["ffn"], cfg, hh)
            return (h, jnp.zeros((), jnp.float32)), None

        (e, _), _ = jax.lax.scan(
            enc_body, (e, jnp.zeros((), jnp.float32)), params["encoder"]
        )
        return layers.norm_fwd(params["enc_final_norm"], cfg, e), e_pos
    if cfg.vision is not None:
        assert patches is not None, "vlm needs patch embeddings (stub frontend)"
        enc_out = layers.norm_fwd(params["vision_norm"], cfg, patches.astype(dtype))
        return enc_out, jnp.arange(patches.shape[1], dtype=jnp.int32)
    return None, None


def forward_hidden(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    *,
    frames: jnp.ndarray | None = None,
    patches: jnp.ndarray | None = None,
    remat: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward WITHOUT the LM head.

    tokens: int32[B, S] → (hidden bf16[B,S,D] post final-norm, aux).
    The loss head is applied chunked in train/step.py so [B,S,V] logits never
    materialise at 150k vocabs.
    """
    b, s = tokens.shape
    positions = jnp.arange(s, dtype=jnp.int32)
    x = params["embed"].astype(jnp.bfloat16)[tokens]
    aux = jnp.zeros((), jnp.float32)
    enc_out, enc_positions = _encode(params, cfg, frames, patches)

    for plan in group_plans(cfg):
        x, a = _scan_group(
            params[plan.name], x, positions, cfg, plan, remat=remat,
            enc_out=enc_out, enc_positions=enc_positions,
        )
        aux = aux + a

    return layers.norm_fwd(params["final_norm"], cfg, x), aux


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    *,
    frames: jnp.ndarray | None = None,
    patches: jnp.ndarray | None = None,
    remat: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward. tokens: int32[B, S] → (logits f32[B,S,V], aux)."""
    x, aux = forward_hidden(
        params, cfg, tokens, frames=frames, patches=patches, remat=remat
    )
    head = (
        params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    ).astype(x.dtype)
    logits = (x @ head).astype(jnp.float32)
    return logits, aux


def mtp_hidden(params, cfg, tokens, hidden):
    """DeepSeek MTP module hidden states: predict token t+2 from
    [h_t ; emb(token_{t+1})]."""
    if not cfg.mtp_depth:
        return None
    p = params["mtp"]
    s = tokens.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)
    nxt = params["embed"].astype(hidden.dtype)[
        jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    ]
    h = jnp.concatenate([hidden, nxt], axis=-1) @ p["proj"].astype(hidden.dtype)
    h, _ = _layer_fwd(p["layer"], cfg, h, positions, "mla" if cfg.mla else "attn", "mlp")
    return layers.norm_fwd(p["norm"], cfg, h)


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16,
               enc_len: int = 0) -> dict:
    cache: dict[str, Any] = {}
    for plan in group_plans(cfg):
        sub = {}
        for i, (mixer, _f) in enumerate(plan.sublayers):
            if mixer in ("attn", "mla", "ssm", "cross"):
                window = cfg.sliding_window if mixer == "attn" else 0
                one = _layer_cache(cfg, mixer, batch, max_seq, window, enc_len, dtype)
                sub[f"s{i}"] = jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (plan.n,) + a.shape).copy()
                    if plan.n > 1
                    else a[None],
                    one,
                )
        cache[plan.name] = sub
    return cache


def decode_step(
    params: dict,
    cfg: ModelConfig,
    token: jnp.ndarray,  # int32 [B]
    cache: dict,
) -> tuple[jnp.ndarray, dict]:
    """One decode step: next-token logits [B, V] + updated cache."""
    x = params["embed"].astype(jnp.bfloat16)[token][:, None, :]
    new_cache: dict[str, Any] = {}
    for plan in group_plans(cfg):
        pgroup = params[plan.name]
        cgroup = cache[plan.name]

        def block_body(h, xs):
            lp, lc = xs
            lc_new = dict(lc)
            for i, (mixer, ffn) in enumerate(plan.sublayers):
                window = cfg.sliding_window if mixer == "attn" else 0
                ci = lc.get(f"s{i}")
                h, c2 = _layer_decode(
                    lp[f"s{i}"], cfg, h, ci, mixer, ffn, window=window
                )
                if c2 is not None:
                    lc_new[f"s{i}"] = c2
            return h, lc_new

        x, cg_new = jax.lax.scan(block_body, x, (pgroup, cgroup))
        new_cache[plan.name] = cg_new
    x = layers.norm_fwd(params["final_norm"], cfg, x)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"]).astype(x.dtype)
    logits = (x[:, 0] @ head).astype(jnp.float32)
    return logits, new_cache


def prefill(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    max_seq: int,
    *,
    frames: jnp.ndarray | None = None,
    patches: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, dict]:
    """Run the prompt, build the cache. Returns (last-token logits, cache).

    Implemented as full-sequence forward + cache writeback: attention layers
    recompute K/V into the cache (cheap relative to the forward itself);
    SSM layers get their final state from the chunked scan.
    """
    b, s = tokens.shape
    positions = jnp.arange(s, dtype=jnp.int32)
    x = params["embed"].astype(jnp.bfloat16)[tokens]
    cache = init_cache(cfg, b, max_seq, enc_len=(
        cfg.encoder.n_frames if cfg.encoder is not None
        else (cfg.vision.n_tokens if cfg.vision is not None else 0)
    ))

    enc_out, enc_positions = _encode(params, cfg, frames, patches)

    new_cache: dict[str, Any] = {}
    for plan in group_plans(cfg):
        pgroup = params[plan.name]
        cgroup = cache[plan.name]

        def block_body(carry, xs):
            h = carry
            h = layers.constrain_seq(h)
            lp, lc = xs
            lc_new = dict(lc)
            for i, (mixer, ffn) in enumerate(plan.sublayers):
                window = cfg.sliding_window if mixer == "attn" else 0
                spec = lp[f"s{i}"]
                if mixer == "attn":
                    hh = layers.norm_fwd(spec["mixer_norm"], cfg, h)
                    q, k, v = layers._project_qkv(spec["mixer"], cfg, hh)
                    k = layers.rope(k, positions, cfg.rope_theta)
                    ci = lc[f"s{i}"]
                    slots = ci["k"].shape[1]
                    if window > 0 and slots < s:
                        ck = ci["k"].at[:, :, :, :].set(
                            jax.lax.dynamic_slice_in_dim(k, s - slots, slots, 1)
                        )
                        cv = ci["v"].at[:, :, :, :].set(
                            jax.lax.dynamic_slice_in_dim(v, s - slots, slots, 1)
                        )
                        spos = jnp.broadcast_to(
                            jnp.arange(s - slots, s, dtype=jnp.int32)[None], (b, slots)
                        )
                        # ring layout: slot = pos % slots
                        order = jnp.argsort(spos[0] % slots)
                        ck, cv = ck[:, order], cv[:, order]
                        spos = spos[:, order]
                    else:
                        ck = ci["k"].at[:, :s].set(k)
                        cv = ci["v"].at[:, :s].set(v)
                        spos = ci["slot_pos"].at[:, :s].set(
                            jnp.arange(s, dtype=jnp.int32)[None]
                        )
                    lc_new[f"s{i}"] = {
                        "k": ck, "v": cv,
                        "pos": jnp.full((b,), s, jnp.int32),
                        "slot_pos": spos,
                    }
                    h, _ = _layer_fwd(spec, cfg, h, positions, mixer, ffn, window=window)
                elif mixer == "mla":
                    hh = layers.norm_fwd(spec["mixer_norm"], cfg, h)
                    _q, ckv1, kr1 = mla._latents(spec["mixer"], cfg, hh, positions)
                    ci = lc[f"s{i}"]
                    lc_new[f"s{i}"] = {
                        "ckv": ci["ckv"].at[:, :s].set(ckv1),
                        "kr": ci["kr"].at[:, :s].set(kr1),
                        "pos": jnp.full((b,), s, jnp.int32),
                    }
                    h, _ = _layer_fwd(spec, cfg, h, positions, mixer, ffn)
                elif mixer == "ssm":
                    hh = layers.norm_fwd(spec["mixer_norm"], cfg, h)
                    y, st = ssm.ssm_fwd(spec["mixer"], cfg, hh)
                    h = h + y
                    if ffn != "none":
                        hh = layers.norm_fwd(spec["ffn_norm"], cfg, h)
                        if ffn == "moe":
                            hh, _a = moe.moe_fwd(spec["ffn"], cfg, hh)
                        else:
                            hh = layers.mlp_fwd(spec["ffn"], cfg, hh)
                        h = h + hh
                    lc_new[f"s{i}"] = st
                elif mixer == "cross":
                    hh = layers.norm_fwd(spec["mixer_norm"], cfg, h)
                    kv_src = enc_out
                    _q, ck, cv = layers._project_qkv(spec["mixer"], cfg, hh, kv_src)
                    lc_new[f"s{i}"] = {"k": ck, "v": cv}
                    h, _ = _layer_fwd(
                        spec, cfg, h, positions, mixer, ffn,
                        enc_out=enc_out, enc_positions=enc_positions,
                    )
                else:
                    h, _ = _layer_fwd(spec, cfg, h, positions, mixer, ffn)
                h = layers.constrain_seq(h)
            return h, lc_new

        x, cg_new = jax.lax.scan(block_body, x, (pgroup, cgroup))
        new_cache[plan.name] = cg_new

    x = layers.norm_fwd(params["final_norm"], cfg, x)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"]).astype(x.dtype)
    logits = (x[:, -1] @ head).astype(jnp.float32)
    return logits, new_cache
