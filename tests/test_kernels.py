"""Pallas kernel tests: interpret-mode vs pure-jnp oracle, shape sweeps."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import xash
from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def rand_rows(n, c, max_len):
    lens = RNG.integers(0, max_len, size=(n, c))
    out = np.zeros((n, c, max_len), dtype=np.uint8)
    for i in range(n):
        for j in range(c):
            out[i, j, : lens[i, j]] = RNG.integers(1, 38, size=lens[i, j])
    return out


@pytest.mark.parametrize("n,c,max_len", [
    (4, 1, 16), (128, 3, 48), (200, 7, 48), (257, 2, 32), (64, 12, 24),
])
def test_superkey_kernel_matches_ref(n, c, max_len):
    cfg = xash.XashConfig(max_len=max_len)
    enc = rand_rows(n, c, max_len)
    got = np.asarray(ops.superkey(enc, cfg))
    want = np.asarray(ref.xash_superkey_ref(jnp.asarray(enc), cfg))
    assert got.shape == (n, cfg.lanes)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("bits", [128, 256, 512])
def test_superkey_kernel_hash_sizes(bits):
    cfg = xash.XashConfig(bits=bits, max_len=32)
    enc = rand_rows(100, 4, 32)
    got = np.asarray(ops.superkey(enc, cfg))
    want = np.asarray(ref.xash_superkey_ref(jnp.asarray(enc), cfg))
    assert np.array_equal(got, want)


def test_xash_values_kernel():
    cfg = xash.DEFAULT_CONFIG
    enc = rand_rows(300, 1, cfg.max_len)[:, 0, :]
    got = np.asarray(ops.xash_values(enc, cfg))
    want = np.asarray(ref.xash_ref(jnp.asarray(enc), cfg))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("n,q", [(10, 3), (1024, 256), (1000, 37), (2049, 300)])
def test_filter_match_kernel(n, q):
    cfg = xash.DEFAULT_CONFIG
    row_sk = np.asarray(
        ref.xash_superkey_ref(jnp.asarray(rand_rows(n, 5, 32)), cfg)
    )
    q_sk = np.asarray(ref.xash_superkey_ref(jnp.asarray(rand_rows(q, 2, 32)), cfg))
    got = np.asarray(ops.filter_match(row_sk, q_sk))
    want = np.asarray(ref.filter_match_ref(jnp.asarray(row_sk), jnp.asarray(q_sk)))
    assert got.shape == (n, q)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("n,q", [(10, 3), (1024, 256), (777, 100)])
def test_filter_count_kernel(n, q):
    cfg = xash.DEFAULT_CONFIG
    row_sk = np.asarray(
        ref.xash_superkey_ref(jnp.asarray(rand_rows(n, 5, 32)), cfg)
    )
    q_sk = np.asarray(ref.xash_superkey_ref(jnp.asarray(rand_rows(q, 2, 32)), cfg))
    got = np.asarray(ops.filter_count(row_sk, q_sk))
    want = np.asarray(ref.filter_count_ref(jnp.asarray(row_sk), jnp.asarray(q_sk)))
    assert np.array_equal(got, want)


def test_filter_count_zero_query_edge():
    cfg = xash.DEFAULT_CONFIG
    row_sk = np.asarray(
        ref.xash_superkey_ref(jnp.asarray(rand_rows(300, 5, 32)), cfg)
    )
    q0 = np.zeros((3, cfg.lanes), dtype=np.uint32)
    got = np.asarray(ops.filter_count(row_sk, q0))
    want = np.asarray(ref.filter_count_ref(jnp.asarray(row_sk), jnp.asarray(q0)))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("bits", [128, 256, 512])
def test_filter_count_all_zero_queries_across_widths(bits):
    """All-zero (empty-string key) query superkeys subsume EVERY row —
    including the rows the wrapper pads in — at any lane count."""
    cfg = xash.XashConfig(bits=bits, max_len=32)
    # 333 rows forces row padding to the 1024 block; 5 queries pads q to 256
    row_sk = np.asarray(ref.xash_superkey_ref(jnp.asarray(rand_rows(333, 4, 32)), cfg))
    q_sk = np.array(ref.xash_superkey_ref(jnp.asarray(rand_rows(5, 2, 32)), cfg))
    q_sk[2] = 0  # zero query mixed among real ones
    got = np.asarray(ops.filter_count(row_sk, q_sk))
    want = np.asarray(ref.filter_count_ref(jnp.asarray(row_sk), jnp.asarray(q_sk)))
    assert np.array_equal(got, want)
    assert got[2] == 333  # vacuous truth: zero query matches every real row


@pytest.mark.parametrize("bits", [128, 256, 512])
@pytest.mark.parametrize("n,q", [(100, 7), (1030, 70)])
def test_filter_count_agrees_with_match_sum(bits, n, q):
    """filter_count == filter_match(...).sum(axis=0) on padded blocks at
    every width (the fused count must equal the materialised reduction)."""
    cfg = xash.XashConfig(bits=bits, max_len=32)
    row_sk = np.asarray(ref.xash_superkey_ref(jnp.asarray(rand_rows(n, 5, 32)), cfg))
    q_sk = np.asarray(ref.xash_superkey_ref(jnp.asarray(rand_rows(q, 2, 32)), cfg))
    counts = np.asarray(ops.filter_count(row_sk, q_sk))
    match = np.asarray(ops.filter_match(row_sk, q_sk))
    assert counts.shape == (q,) and match.shape == (n, q)
    assert np.array_equal(counts, match.sum(axis=0, dtype=np.int32))


@pytest.mark.parametrize("bits", [128, 256, 512])
def test_filter_hits_table_counts_matches_oracle(bits, monkeypatch):
    """Device-side rule-1/2 reduction == host oracle at every width and on
    every dispatch path (numpy / XLA / interpret-mode Pallas), on shapes
    that force pow2 padding of rows, queries and table segments."""
    cfg = xash.XashConfig(bits=bits, max_len=32)
    rng = np.random.default_rng(bits)
    n, q, n_tables = 700, 23, 19
    row_sk = np.asarray(ref.xash_superkey_ref(jnp.asarray(rand_rows(n, 5, 32)), cfg))
    q_sk = np.asarray(ref.xash_superkey_ref(jnp.asarray(rand_rows(q, 2, 32)), cfg))
    elig = ops.Eligibility(
        rng.integers(0, 2, size=n).astype(np.int32),
        rng.integers(0, 2, size=q).astype(np.int32),
    )
    seg = np.sort(rng.integers(0, n_tables, size=n)).astype(np.int32)
    want_hits = ops.subsume_np(row_sk, q_sk) & elig.dense()
    want_counts = np.bincount(
        seg, weights=want_hits.sum(axis=1), minlength=n_tables
    ).astype(np.int32)
    for backend in ("numpy", "xla", "pallas"):
        monkeypatch.setenv("MATE_FILTER_BACKEND", backend)
        hits, counts = ops.filter_hits_table_counts(
            row_sk, q_sk, elig, seg, n_tables
        )
        assert np.array_equal(np.asarray(hits), want_hits), (bits, backend)
        assert np.array_equal(counts, want_counts), (bits, backend)
    monkeypatch.delenv("MATE_FILTER_BACKEND")
    hits, counts = ops.filter_hits_table_counts(
        row_sk, q_sk, elig, seg, n_tables, use_device=False
    )
    assert np.array_equal(np.asarray(hits), want_hits)
    assert np.array_equal(counts, want_counts)


@pytest.mark.parametrize("s,d,dv,window,dtype", [
    (256, 64, 64, 0, jnp.float32),
    (256, 64, 64, 64, jnp.float32),
    (384, 128, 64, 0, jnp.bfloat16),  # MLA-style dv != d, unaligned S
])
def test_flash_attention_kernel(s, d, dv, window, dtype):
    import jax

    rng = jax.random.PRNGKey(0)
    B, H = 2, 2
    q = jax.random.normal(rng, (B, s, H, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, s, H, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, s, H, dv), dtype)
    out = ops.flash_attention(q, k, v, causal=True, window=window)
    sc = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) / np.sqrt(d)
    diff = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    ok = diff >= 0
    if window:
        ok = ok & (diff < window)
    sc = jnp.where(ok[None, None], sc, -1e30)
    ref = jnp.einsum(
        "bhst,bthd->bshd", jax.nn.softmax(sc, -1).astype(dtype), v
    )
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    assert float(
        jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    ) < tol


def test_filter_block_shape_sweep():
    cfg = xash.DEFAULT_CONFIG
    row_sk = np.asarray(
        ref.xash_superkey_ref(jnp.asarray(rand_rows(512, 4, 32)), cfg)
    )
    q_sk = np.asarray(ref.xash_superkey_ref(jnp.asarray(rand_rows(64, 2, 32)), cfg))
    want = np.asarray(ref.filter_match_ref(jnp.asarray(row_sk), jnp.asarray(q_sk)))
    for bn, bq in [(128, 64), (256, 128), (512, 64)]:
        got = np.asarray(ops.filter_match(row_sk, q_sk, block_n=bn, block_q=bq))
        assert np.array_equal(got, want), (bn, bq)
