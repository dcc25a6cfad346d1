"""Inverted index: postings, super keys, §5.4 updates, distributed filter."""

import jax
import numpy as np
import pytest

from repro.core import discovery, distributed, xash
from repro.core.corpus import Corpus, Table
from repro.core.index import MateIndex
from repro.data import synthetic


def small_corpus():
    return Corpus(
        [
            Table(0, [["uk", "cambridge", "x"], ["japan", "tokyo", "y"]]),
            Table(1, [["uk", "oxford", "z"]]),
        ]
    )


def test_postings_locations():
    idx = MateIndex(small_corpus())
    pl = idx.fetch_postings("uk")
    assert sorted(map(tuple, pl.tolist())) == [(0, 0), (2, 0)]  # global rows 0,2
    assert len(idx.fetch_postings("nonexistent")) == 0


def test_superkey_is_or_of_cells():
    corpus = small_corpus()
    idx = MateIndex(corpus)
    want = 0
    for v in ["uk", "cambridge", "x"]:
        want |= xash.xash_oracle(v, idx.cfg)
    assert xash.lanes_to_int(idx.superkeys[0]) == want


def test_insert_table():
    corpus = small_corpus()
    idx = MateIndex(corpus)
    tid = idx.insert_table([["uk", "cambridge", "new"], ["france", "paris", "w"]])
    assert tid == 2
    pl = idx.fetch_postings("uk")
    assert len(pl) == 3
    # new rows discoverable
    q = Table(-1, [["uk", "cambridge"]])
    topk, _ = discovery.discover(idx, q, [0, 1], k=5)
    assert tid in [e.table_id for e in topk]


def test_delete_table():
    idx = MateIndex(small_corpus())
    idx.delete_table(0)
    pl = idx.fetch_postings("uk")
    assert [tuple(x) for x in pl.tolist()] == [(2, 0)]


def test_update_cell_rehashes():
    corpus = small_corpus()
    idx = MateIndex(corpus)
    old_sk = idx.superkeys[0].copy()
    idx.update_cell(0, 0, 1, "london")
    assert not np.array_equal(old_sk, idx.superkeys[0])
    assert len(idx.fetch_postings("cambridge")) == 0
    assert len(idx.fetch_postings("london")) == 1
    want = 0
    for v in ["uk", "london", "x"]:
        want |= xash.xash_oracle(v, idx.cfg)
    assert xash.lanes_to_int(idx.superkeys[0]) == want


def _assert_same_index_state(idx: MateIndex, rebuilt: MateIndex):
    """Incrementally-updated index must equal one built from scratch."""
    assert np.array_equal(idx.superkeys, rebuilt.superkeys)
    for value in rebuilt.corpus.value_of:
        got = sorted(map(tuple, idx.fetch_postings(value).tolist()))
        want = sorted(map(tuple, rebuilt.fetch_postings(value).tolist()))
        assert got == want, value


def test_insert_table_matches_rebuild():
    idx = MateIndex(small_corpus())
    new_cells = [["uk", "cambridge", "new"], ["france", "paris", "w"]]
    idx.insert_table(new_cells)
    rebuilt = MateIndex(
        Corpus(
            [
                Table(0, [["uk", "cambridge", "x"], ["japan", "tokyo", "y"]]),
                Table(1, [["uk", "oxford", "z"]]),
                Table(2, new_cells),
            ]
        )
    )
    _assert_same_index_state(idx, rebuilt)


def test_update_cell_matches_rebuild():
    idx = MateIndex(small_corpus())
    idx.update_cell(0, 0, 1, "london")
    idx.update_cell(1, 0, 2, "tokyo")  # now shares a value with table 0
    rebuilt = MateIndex(
        Corpus(
            [
                Table(0, [["uk", "london", "x"], ["japan", "tokyo", "y"]]),
                Table(1, [["uk", "oxford", "tokyo"]]),
            ]
        )
    )
    _assert_same_index_state(idx, rebuilt)


def test_delete_table_matches_rebuild():
    """Tombstoned tables vanish from discovery exactly like a rebuild
    without them (modulo the table-id shift a rebuild causes)."""
    corpus = synthetic.make_corpus(synthetic.SyntheticSpec(n_tables=80, seed=5))
    query, q_cols, expected, corpus = synthetic.make_query_with_ground_truth(corpus)
    idx = MateIndex(corpus)
    topk, _ = discovery.discover(idx, query, q_cols, k=5)
    victim = topk[0].table_id
    idx.delete_table(victim)

    kept = [t for t in corpus.tables if t.table_id != victim]
    new_id = {t.table_id: i for i, t in enumerate(kept)}
    rebuilt = MateIndex(
        Corpus([Table(new_id[t.table_id], t.cells, t.name) for t in kept])
    )
    got, _ = discovery.discover(idx, query, q_cols, k=5)
    want, _ = discovery.discover(rebuilt, query, q_cols, k=5)
    assert victim not in [e.table_id for e in got]
    assert [(new_id[e.table_id], e.joinability) for e in got] == [
        (e.table_id, e.joinability) for e in want
    ]


def test_updates_keep_engines_bit_identical():
    """After a mix of §5.4 updates, scalar and batched engines still agree."""
    from repro.core.batched import discover_many

    corpus = synthetic.make_corpus(synthetic.SyntheticSpec(n_tables=60, seed=9))
    query, q_cols, _, corpus = synthetic.make_query_with_ground_truth(corpus)
    idx = MateIndex(corpus)
    key_cells = [[query.cells[r][c] for c in q_cols] for r in range(query.n_rows)]
    tid = idx.insert_table([kc + ["extra"] for kc in key_cells])
    idx.update_cell(tid, 0, len(key_cells[0]), "mutated")
    idx.delete_table(0)

    seq, _ = discovery.discover(idx, query, q_cols, k=8)
    assert tid in [e.table_id for e in seq]
    for backend in ("numpy", None):
        bat, _ = discover_many(idx, [(query, q_cols)], k=8, backend=backend)[0]
        assert [(e.table_id, e.joinability, e.mapping) for e in seq] == [
            (e.table_id, e.joinability, e.mapping) for e in bat
        ]
    [(many, _)] = discover_many(idx, [(query, q_cols)], k=8)
    assert [(e.table_id, e.joinability) for e in many] == [
        (e.table_id, e.joinability) for e in seq
    ]


def test_gather_candidates_matches_scalar_grouping():
    """CSR block == the scalar engine's per-value dict grouping."""
    corpus = synthetic.make_corpus(synthetic.SyntheticSpec(n_tables=40, seed=3))
    idx = MateIndex(corpus)
    queries = synthetic.make_mixed_queries(corpus, 1, 15, 2, seed=4)
    (q, q_cols) = queries[0]
    init_col = discovery.init_column_selection(q, q_cols, "cardinality", idx)
    values = list(dict.fromkeys(q.column(init_col)))

    by_table = {}
    for i, v in enumerate(values):
        for grow, _col in idx.fetch_postings(v).tolist():
            by_table.setdefault(int(idx.corpus.table_of_row(grow)), []).append(
                (int(grow), i)
            )
    order = sorted(by_table, key=lambda t: (-len(by_table[t]), t))

    block = idx.gather_candidates(values)
    assert block.table_ids.tolist() == order
    assert block.n_items == sum(len(v) for v in by_table.values())
    for t, tid in enumerate(order):
        s = block.table_slice(t)
        got = list(zip(block.rows[s].tolist(), block.value_idx[s].tolist()))
        assert sorted(got) == sorted(by_table[tid])


def test_superkey_of_keys_matches_per_value_or():
    corpus = small_corpus()
    idx = MateIndex(corpus)
    keys = [("uk", "cambridge"), ("japan", "tokyo"), ("uk", "oxford")]
    got = idx.superkey_of_keys(keys)
    for i, key in enumerate(keys):
        want = 0
        for v in key:
            want |= xash.xash_oracle(v, idx.cfg)
        assert xash.lanes_to_int(got[i]) == want


@pytest.mark.parametrize("hash_name", ["xash", "murmur"])
def test_superkey_of_keys_ragged_widths_raise(hash_name):
    """Regression: a ragged n-ary key list used to crash in the xash
    branch's reshape and silently mis-hash on the baseline OR path — both
    branches must raise the same clear ValueError."""
    idx = MateIndex(small_corpus(), hash_name=hash_name)
    with pytest.raises(ValueError, match="ragged key widths"):
        idx.superkey_of_keys([("uk", "cambridge"), ("japan",)])
    with pytest.raises(ValueError, match="key 2 has 3"):
        idx.superkey_of_keys([("uk",), ("japan",), ("uk", "oxford", "z")])
    # uniform widths (any width) still hash fine
    assert idx.superkey_of_keys([("uk",), ("japan",)]).shape == (2, idx.cfg.lanes)


def test_fetch_postings_deleted_mask_cached_on_epoch():
    """The tombstone filter uses a deleted-row mask cached on
    mutation_epoch — behavior-neutral vs the old per-fetch np.isin, and
    rebuilt exactly once per epoch even under delete-heavy fetch storms."""
    corpus = synthetic.make_corpus(synthetic.SyntheticSpec(n_tables=40, seed=11))
    idx = MateIndex(corpus)
    victims = list(range(0, 40, 3))  # delete-heavy: 14 tombstoned tables
    for t in victims:
        idx.delete_table(t)
    epoch = idx.mutation_epoch
    for value in list(idx.corpus.value_of)[:200]:
        got = idx.fetch_postings(value)
        # the replaced per-fetch semantics: isin against the tombstone set
        vid = idx.corpus.value_of.get(value)
        pl = idx.postings.get(vid, np.zeros((0, 2), np.int64))
        if len(pl):
            tids = idx.corpus.table_of_row(pl[:, 0])
            pl = pl[~np.isin(tids, list(idx._deleted_tables))]
        assert np.array_equal(got, pl), value
    # the mask was built once for the whole storm, keyed on the epoch
    assert idx._deleted_mask_epoch == epoch
    mask = idx._deleted_mask
    idx.fetch_postings(next(iter(idx.corpus.value_of)))
    assert idx._deleted_mask is mask  # no rebuild within an epoch
    idx.delete_table(39)  # epoch bump → next fetch rebuilds
    idx.fetch_postings(next(iter(idx.corpus.value_of)))
    assert idx._deleted_mask is not mask
    assert idx._deleted_mask_epoch == idx.mutation_epoch


def test_corpus_char_frequencies():
    corpus = small_corpus()
    freq = corpus.char_frequencies()
    assert freq.shape == (37,)
    assert abs(freq.sum() - 1.0) < 1e-9
    idx = MateIndex(corpus, use_corpus_char_freq=True)
    assert idx.cfg.char_freq is not None


def test_distributed_filter_matches_local():
    corpus = synthetic.make_corpus(synthetic.SyntheticSpec(n_tables=60, seed=1))
    idx = MateIndex(corpus)
    queries = synthetic.make_mixed_queries(corpus, 1, 10, 2, seed=2)
    q, q_cols = queries[0]
    _keys, sk_of_key = discovery.build_query_superkeys(idx, q, q_cols)
    qsk = np.stack(list(sk_of_key.values()))
    row_tables = np.asarray(
        corpus.table_of_row(np.arange(corpus.total_rows)), dtype=np.int32
    )
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    sk, rt = distributed.shard_corpus_rows(idx.superkeys, row_tables, mesh, ("data",))
    fn = distributed.make_distributed_filter(mesh, len(corpus.tables), ("data",))
    tc, kc = fn(sk, rt, qsk)
    tc_ref, kc_ref = distributed.filter_counts_local(
        idx.superkeys, row_tables, qsk, len(corpus.tables)
    )
    assert np.array_equal(np.asarray(tc), np.asarray(tc_ref))
    assert np.array_equal(np.asarray(kc), np.asarray(kc_ref))
