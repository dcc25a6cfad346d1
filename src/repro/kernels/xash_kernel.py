"""Pallas TPU kernel for XASH hashing + super-key OR-aggregation (paper §5).

Offline indexing hashes every cell of the corpus — billions of values for
DWTC-scale lakes — so it is the throughput-critical half of MATE.  The kernel
fuses, per row block:

    for each cell:  character stats → rare-char selection → bit positions
                    (Eq. 6/7 + rotation) → 128-bit one-hot
    OR-aggregate cells → pack to uint32 lanes

entirely in VMEM, writing only the final ``[lanes, block]`` super keys to HBM
(48·C bytes read, 16 bytes written per row — no intermediate materialisation).

TPU notes:
  * the rare-char arg-min is implemented as (min, compare, masked-sum) —
    no gathers, no sorts; scores are unique by construction (count*64+rank,
    rank a permutation of 0..36) so the compare selects exactly one char;
  * everything is VPU work on [block, 37]/[block, 128] tiles; MXU is unused
    (this is not a matmul workload);
  * the cell loop is a ``fori_loop`` with the 128-wide accumulator carried in
    vregs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import encoding
from repro.core.xash import XashConfig

DEFAULT_BLOCK_N = 128


def _cell_bits(cell, rank_row, cfg: XashConfig):
    """bits: bool[bn, bits] for one cell slice ``cell`` int32[bn, L]."""
    a = encoding.ALPHABET_SIZE
    bn, max_len = cell.shape
    cbits, region, lseg = cfg.c, cfg.char_region, cfg.len_segment
    BIG = jnp.int32(1 << 24)

    is_char = cell > 0
    l_v = jnp.sum(is_char.astype(jnp.int32), axis=-1)  # [bn]

    iota_a = jax.lax.broadcasted_iota(jnp.int32, (bn, max_len, a), 2)
    onehot = (cell[:, :, None] == iota_a + 1) & is_char[:, :, None]
    onehot_i = onehot.astype(jnp.int32)
    count = jnp.sum(onehot_i, axis=1)  # [bn, a]
    pos_w = jax.lax.broadcasted_iota(jnp.int32, (bn, max_len, a), 1) + 1
    sum_pos = jnp.sum(onehot_i * pos_w, axis=1)  # [bn, a]

    score = jnp.where(count > 0, count * 64 + rank_row[None, :], BIG)
    iota_char = jax.lax.broadcasted_iota(jnp.int32, (bn, a), 1)
    iota_bits = jax.lax.broadcasted_iota(jnp.int32, (bn, cfg.bits), 1)

    bits = jnp.zeros((bn, cfg.bits), dtype=jnp.bool_)
    for _pick in range(cfg.n_char_bits):
        m = jnp.min(score, axis=-1, keepdims=True)  # [bn, 1]
        sel = score == m  # exactly one True per row (scores unique)
        chosen_count = jnp.sum(count * sel, axis=-1)
        chosen_sum = jnp.sum(sum_pos * sel, axis=-1)
        chosen_id = jnp.sum(iota_char * sel, axis=-1)
        denom = jnp.maximum(chosen_count * l_v, 1)
        x = -((-chosen_sum * cbits) // denom)
        x = jnp.clip(x, 1, cbits)
        p = chosen_id * cbits + (x - 1)
        p_rot = jnp.remainder(p - l_v, region)
        bitpos = lseg + p_rot  # [bn]
        valid = (m[:, 0] < BIG) & (l_v > 0)
        bits = bits | ((iota_bits == bitpos[:, None]) & valid[:, None])
        score = jnp.where(sel, BIG, score)

    len_bit = jnp.remainder(l_v, lseg)
    bits = bits | ((iota_bits == len_bit[:, None]) & (l_v > 0)[:, None])
    return bits


def _superkey_kernel(enc_ref, rank_ref, out_ref, *, cfg: XashConfig, n_cols: int):
    bn = enc_ref.shape[0]
    rank_row = rank_ref[0, :]  # [37]

    def body(c, acc):
        cell = enc_ref[:, pl.ds(c, 1), :].reshape(bn, enc_ref.shape[2])
        return acc | _cell_bits(cell, rank_row, cfg)

    bits = jax.lax.fori_loop(
        0, n_cols, body, jnp.zeros((bn, cfg.bits), dtype=jnp.bool_)
    )
    # pack bool[bn, bits] -> uint32[lanes, bn]
    lanes = cfg.lanes
    grouped = bits.reshape(bn, lanes, 32).astype(jnp.uint32)
    weights = jnp.left_shift(
        jnp.uint32(1), jax.lax.broadcasted_iota(jnp.uint32, (bn, lanes, 32), 2)
    )
    packed = jnp.sum(grouped * weights, axis=-1, dtype=jnp.uint32)  # [bn, lanes]
    out_ref[...] = packed.T


@functools.partial(jax.jit, static_argnames=("cfg", "block_n", "interpret"))
def xash_superkey(
    enc: jnp.ndarray,
    rank: jnp.ndarray,
    cfg: XashConfig,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jnp.ndarray:
    """Super keys for encoded rows.

    Args:
      enc: int32[n, n_cols, max_len], n divisible by block_n.
      rank: int32[1, 37] ascending-frequency char ranks.
    Returns:
      uint32[lanes, n] (transposed layout; ops.py untransposes).
    """
    n, n_cols, max_len = enc.shape
    grid = (n // block_n,)
    return pl.pallas_call(
        functools.partial(_superkey_kernel, cfg=cfg, n_cols=n_cols),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, n_cols, max_len), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, encoding.ALPHABET_SIZE), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((cfg.lanes, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((cfg.lanes, n), jnp.uint32),
        interpret=interpret,
        name="xash_superkey",
    )(enc, rank)
