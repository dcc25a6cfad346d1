"""Eligibility in id form: a filter launch takes each item's and each key's
init-value id, and the kernels form every tile's mask as ``item == key``.

Per-table counts from the two id vectors must equal, bit for bit, the counts
from the dense ``[items, keys]`` matrix they stand for: padding rows and
keys (saturated row super keys included), a group's block-diagonal, a launch
split into table chunks, and a lane-prefix degrade.  Planning must give the
same eligible pairs as the dense block it used to build.
"""

import numpy as np
import pytest

from conftest import mixed_query_lake
from repro.core import batched as B
from repro.core import discovery, xash
from repro.core.index import MateIndex
from repro.kernels import ops

# per case: (items, keys, init values) of each plan in the launch, the
# table count, and the lanes probed of a store this many lanes wide
CASES = {
    # 700 items pad to 1024 rows, 23 keys to 64 columns
    "padding": ([(700, 23, 3)], 19, 4, 4),
    # three requests in one launch: ids offset per plan
    "group": ([(300, 7, 4), (500, 30, 9), (120, 3, 2)], 19, 4, 4),
    # 13 tables over a scatter-tile cap of 4: four launches
    "table_chunks": ([(900, 40, 5)], 13, 4, 4),
    # 4 lanes probed over a 16-lane store
    "lane_prefix": ([(600, 31, 3)], 11, 16, 4),
}


def _case(name):
    """The store, the launch's rows, keys, segment ids, its dense
    eligibility assembled as the block-diagonal of each plan's block, and
    the same eligibility as offset ids."""
    plans, n_tables, store_lanes, lanes = CASES[name]
    rng = np.random.default_rng(len(name))
    store = rng.integers(0, 2**32, size=(2048, store_lanes), dtype=np.uint32)
    store[:32] = 0xFFFFFFFF  # saturated rows subsume every key, padding too
    n = sum(p[0] for p in plans)
    q = sum(p[1] for p in plans)
    rows = rng.integers(0, store.shape[0], size=n).astype(np.int64)
    rows[::50] = np.arange(len(rows[::50])) % 32
    q_sk = rng.integers(0, 2**32, size=(q, store_lanes), dtype=np.uint32)
    for k in range(0, q, 2):  # plant subsuming keys so counts are not all 0
        q_sk[k] = store[rows[(7 * k) % n]] & rng.integers(
            0, 2**32, size=store_lanes, dtype=np.uint32
        )
    dense = np.zeros((n, q), dtype=bool)
    items, keys = [], []
    r_off = k_off = v_off = 0
    for ni, ki, vi in plans:
        item = rng.integers(0, vi, size=ni).astype(np.int32)
        key = rng.integers(0, vi, size=ki).astype(np.int32)
        dense[r_off : r_off + ni, k_off : k_off + ki] = item[:, None] == key
        items.append(item + v_off)
        keys.append(key + v_off)
        r_off, k_off, v_off = r_off + ni, k_off + ki, v_off + vi
    elig = ops.Eligibility(np.concatenate(items), np.concatenate(keys))
    seg = np.sort(rng.integers(0, n_tables, size=n)).astype(np.int32)
    return store, rows, q_sk[:, :lanes], dense, elig, seg, n_tables, lanes


def _dense_counts(row_sk, q_sk, dense, seg, n_tables):
    hits = ops.subsume_np(row_sk, q_sk) & dense
    return np.bincount(
        seg, weights=hits.sum(axis=1), minlength=n_tables
    ).astype(np.int32)


def _launch(case, monkeypatch, backend):
    store, rows, q_sk, dense, elig, seg, n_tables, lanes = _case(case)
    assert dense.any() and not dense.all()
    assert np.array_equal(elig.dense(), dense)
    if case == "table_chunks":
        monkeypatch.setattr(ops, "_FUSED_MAX_TABLES", 4)
    row_sk = store[rows][:, :lanes]
    want = _dense_counts(row_sk, q_sk, dense, seg, n_tables)
    assert want.any()
    if backend == "fused-gather":
        pairs, got = ops.filter_hits_table_counts(
            None, q_sk, elig, seg, n_tables, backend=backend,
            store=ops.device_store(store), rows=rows,
        )
        # the launch's own survivors: the dense block's hits, item-major
        want_pairs = np.nonzero(ops.subsume_np(row_sk, q_sk) & dense)
        assert np.array_equal(pairs.rows, want_pairs[0])
        assert np.array_equal(pairs.keys, want_pairs[1])
    else:
        hits, got = ops.filter_hits_table_counts(
            row_sk, q_sk, elig, seg, n_tables, backend=backend
        )
        assert hits is None
    return got, want


@pytest.mark.parametrize("case", sorted(CASES))
def test_table_counts_kernel_ids_equal_dense(case, monkeypatch):
    """``filter_kernel.filter_table_counts`` from the id operands."""
    got, want = _launch(case, monkeypatch, "fused")
    assert np.array_equal(got, want), case


@pytest.mark.parametrize("case", sorted(CASES))
def test_gather_kernel_ids_equal_dense(case, monkeypatch):
    """``filter_kernel.gather_filter_table_counts`` from the id operands:
    its counts, and the pairs its packed hit tile returns."""
    got, want = _launch(case, monkeypatch, "fused-gather")
    assert np.array_equal(got, want), case


@pytest.fixture(scope="module")
def lake():
    corpus, queries = mixed_query_lake(n_tables=60, n_rows=30)
    return MateIndex(corpus, cfg=xash.XashConfig(bits=128)), queries


def test_plan_eligibility_equals_the_dense_block(lake):
    """``plan.elig.dense()`` is the block planning used to build,
    ``elig_value[block.value_idx]``; ``filter_checks`` from the CSR is its
    sum."""
    index, queries = lake
    for query, q_cols in queries:
        plan = B.plan_query(index, query, q_cols)
        init_col = discovery.init_column_selection(
            query, q_cols, "cardinality", index
        )
        init_idx = q_cols.index(init_col)
        values = list(dict.fromkeys(query.column(init_col)))
        elig_value = np.array(
            [[key[init_idx] == v for key in plan.distinct_keys] for v in values],
            dtype=bool,
        ).reshape(len(values), len(plan.distinct_keys))
        want = elig_value[plan.block.value_idx]
        assert want.any()
        assert np.array_equal(plan.elig.dense(), want)
        assert B.eligible_count(plan, plan.block.value_idx) == int(want.sum())
        (pc,) = B.plan_and_count(index, [(query, q_cols)], "fused")
        _, stats = B.score_from_counts(index, pc)
        assert stats.filter_checks == int(want.sum())


def test_group_launch_eligibility_is_block_diagonal(lake, monkeypatch):
    """A group launch's ids keep each request's keys on its own items: the
    dense view is the block-diagonal of the plans' blocks, and each
    request's counts equal its solo launch."""
    index, queries = lake
    queries = queries[:3]
    seen = []
    launch = ops.filter_hits_table_counts

    def spy(row_sk, query_sk, elig, seg_ids, n_tables, **kwargs):
        seen.append(elig)
        return launch(row_sk, query_sk, elig, seg_ids, n_tables, **kwargs)

    monkeypatch.setattr(ops, "filter_hits_table_counts", spy)
    pcs = B.plan_and_count(index, queries, "fused")
    (elig,) = seen
    blocks = [pc.plan.elig.dense() for pc in pcs]
    want = np.zeros(tuple(map(sum, zip(*(b.shape for b in blocks)))), dtype=bool)
    r = k = 0
    for b in blocks:
        want[r : r + b.shape[0], k : k + b.shape[1]] = b
        r, k = r + b.shape[0], k + b.shape[1]
    assert np.array_equal(elig.dense(), want)
    for pc, query in zip(pcs, queries):
        (solo,) = B.plan_and_count(index, [query], "fused")
        assert np.array_equal(pc.counts, solo.counts)
