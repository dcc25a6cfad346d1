"""Cross-backend differential conformance suite (ISSUE 10).

One seeded discovery scenario, parametrized over EVERY backend registered in
``kernels/registry.py`` × every superkey width, asserting each backend
reproduces the numpy reference bit-identically across all four engine
surfaces:

  * ``discover_many`` on one request — entry sequence (count rank: fully
    deterministic);
  * ``discover_many`` — per-request entry sequences under the shared launch;
  * two-phase ``plan_and_count`` + ``score_from_counts`` — the per-table
    COUNT VECTORS themselves (the §6.3 filter is exact bitwise arithmetic,
    so even intermediate counts may not drift) and the scored entries at
    two different k;
  * ``core.fd.discover_fds`` — FD verdict tuples on a planted-FD lake.

Plus the stats invariants that define each dispatch class: fused backends
never materialise a match matrix (``filter_matrix_bytes == 0``), non-fused
ones always do (on non-empty candidate sets).

Backend drift used to surface only in scattered per-feature suites
(test_gather_fused, test_routed, ...) — this module is the single net: a
NEW backend registered tomorrow is pulled in automatically via
``registry.backend_names()`` and must conform everywhere before CI passes.

The lake is deliberately tiny: the pallas/fused legs run interpret-mode on
CPU, so per-test cost is dominated by kernel interpretation.
"""

import numpy as np
import pytest

from repro.core import batched, fd, xash
from repro.core.index import build_index
from repro.kernels import registry

from conftest import ALL_BITS, mixed_query_lake
from test_fd import planted_fd_lake, _entry_key

BACKENDS = registry.backend_names()
K = 5


def _key(entries):
    return [(e.table_id, e.joinability, e.mapping) for e in entries]


@pytest.fixture(scope="module")
def lake():
    corpus, queries = mixed_query_lake(
        n_tables=30, corpus_seed=3, n_queries=2, n_rows=8, key_width=2,
        query_seed=5,
    )
    assert len(queries) == 2
    return corpus, queries


@pytest.fixture(scope="module")
def built(lake):
    corpus, _ = lake
    return {
        bits: build_index(corpus, cfg=xash.XashConfig(bits=bits))[0]
        for bits in ALL_BITS
    }


@pytest.fixture(scope="module")
def fd_lake():
    corpus, query, det_cols, dep_col = planted_fd_lake(3)
    indexes = {
        bits: build_index(corpus, cfg=xash.XashConfig(bits=bits))[0]
        for bits in ALL_BITS
    }
    return indexes, query, det_cols, dep_col


@pytest.fixture(scope="module")
def reference(lake, built, fd_lake):
    """Numpy-backend ground truth per width, computed once."""
    _, queries = lake
    fd_idx, fd_query, det_cols, dep_col = fd_lake
    ref = {}
    for bits in ALL_BITS:
        idx = built[bits]
        single, _ = batched.discover_many(
            idx, [(queries[0][0], queries[0][1])], k=K, backend="numpy"
        )[0]
        many = batched.discover_many(idx, queries, k=K, backend="numpy")
        pcs = batched.plan_and_count(idx, queries, "numpy")
        counts = [np.asarray(pc.counts).copy() for pc in pcs]
        scored = {
            kk: [
                _key(batched.score_from_counts(idx, pc, kk)[0]) for pc in pcs
            ]
            for kk in (K, 3)
        }
        fds, _ = fd.discover_fds(
            fd_idx[bits], fd_query, det_cols, dep_col, backend="numpy"
        )
        ref[bits] = {
            "single": _key(single),
            "many": [_key(entries) for entries, _ in many],
            "counts": counts,
            "scored": scored,
            "fds": _entry_key(fds),
        }
    return ref


@pytest.mark.parametrize("bits", ALL_BITS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_conforms(lake, built, fd_lake, reference, backend, bits):
    _, queries = lake
    idx = built[bits]
    ref = reference[bits]
    bk = registry.resolve_backend(backend)

    # -- discover: bit-identical entry sequence + matrix invariant --------
    single, st = batched.discover_many(
        idx, [(queries[0][0], queries[0][1])], k=K, backend=bk
    )[0]
    assert _key(single) == ref["single"], "discover drifted"
    if bk.fused:
        assert st.filter_matrix_bytes == 0, (
            "fused dispatch materialised a match matrix"
        )
    elif st.filter_checks:
        assert st.filter_matrix_bytes > 0

    # -- discover_many: every request bit-identical -----------------------
    many = batched.discover_many(idx, queries, k=K, backend=bk)
    assert [_key(entries) for entries, _ in many] == ref["many"]

    # -- two-phase: the COUNT VECTORS must match, then scoring at two k ---
    pcs = batched.plan_and_count(idx, queries, bk)
    for pc, ref_counts in zip(pcs, ref["counts"]):
        np.testing.assert_array_equal(np.asarray(pc.counts), ref_counts)
    for kk in (K, 3):
        got = [
            _key(batched.score_from_counts(idx, pc, kk)[0]) for pc in pcs
        ]
        assert got == ref["scored"][kk]
    if bk.fused:
        for pc in pcs:
            _, st2 = batched.score_from_counts(idx, pc, K)
            assert st2.filter_matrix_bytes == 0

    # -- FD workload: verdict tuples bit-identical ------------------------
    fd_idx, fd_query, det_cols, dep_col = fd_lake
    fds, fd_st = fd.discover_fds(
        fd_idx[bits], fd_query, det_cols, dep_col, backend=bk
    )
    assert _entry_key(fds) == ref["fds"], "FD verdicts drifted"
    if bk.fused:
        assert fd_st.filter_matrix_bytes == 0
