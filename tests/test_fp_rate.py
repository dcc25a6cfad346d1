"""FP-rate regression across superkey widths (paper Tables 1-2 ordering).

Pins the precision/bandwidth tradeoff the 512-bit path exists for: on a
seeded synthetic lake, widening the hash must strictly cut false-positive
rows, and NO width may ever reject an exact match (§6.3 lemma).
"""

import pytest

from repro.core.batched import discover_many, filter_outcomes

from conftest import mixed_query_lake, indexes_at_widths

WIDTHS = (128, 256, 512)


@pytest.fixture(scope="module")
def fp_lake():
    """FP-heavy workload: mixed queries whose key columns come from
    different tables, so single columns hit many posting lists while full
    composite keys rarely exist (the paper's sensor-data regime).
    One index per width, shared by every test in this module."""
    corpus, queries = mixed_query_lake(
        n_tables=120, corpus_seed=7, n_queries=4, n_rows=20, key_width=2,
        query_seed=11,
    )
    assert queries
    # lazy-profile indexes (built=False): this module never ranks or gates
    indexes = indexes_at_widths(corpus, WIDTHS, built=False)
    outcomes = {}
    for bits, index in indexes.items():
        agg = {"checks": 0, "passed": 0, "tp": 0, "fp": 0, "fn": 0}
        for q, q_cols in queries:
            out = filter_outcomes(index, q, q_cols, check_false_negatives=True)
            for k in agg:
                agg[k] += out[k]
        outcomes[bits] = agg
    return queries, indexes, outcomes


def test_512bit_strictly_fewer_false_positives(fp_lake):
    _, _, outcomes = fp_lake
    agg128, agg512 = outcomes[128], outcomes[512]
    # identical probe workload at both widths
    assert agg128["checks"] == agg512["checks"] > 0
    # the ordering the paper's Tables 1-2 report: wider hash, fewer FPs
    assert agg128["fp"] > 0, "lake must exercise the FP regime"
    assert agg512["fp"] < agg128["fp"]
    # exact matches are width-invariant
    assert agg128["tp"] == agg512["tp"] > 0


def test_no_false_negatives_at_any_width(fp_lake):
    _, _, outcomes = fp_lake
    for bits in WIDTHS:
        assert outcomes[bits]["fn"] == 0, bits


def test_fp_ordering_survives_topk_engine(fp_lake):
    """The engine-level verified-FP stat shows the same ordering, and both
    widths return the same top-k (FP rate never changes results)."""
    queries, indexes, _ = fp_lake
    fp128 = fp512 = 0
    for q, q_cols in queries:
        top128, st128 = discover_many(indexes[128], [(q, q_cols)], k=5)[0]
        top512, st512 = discover_many(indexes[512], [(q, q_cols)], k=5)[0]
        assert [(e.table_id, e.joinability) for e in top128] == [
            (e.table_id, e.joinability) for e in top512
        ]
        fp128 += st128.verified_fp
        fp512 += st512.verified_fp
    assert fp512 <= fp128
