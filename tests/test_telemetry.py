"""The program's own spans and counters (``repro.telemetry``).

Off by default and free while off; while on, one request served through
``AsyncDiscoveryEngine`` records the documented span tree with one shared
request id, its counters agree with ``DiscoveryStats``, and a program
lowered inside a span is recorded under that span.
"""

import asyncio

import jax
import jax.numpy as jnp
import pytest

from repro import telemetry
from repro.core.session import DiscoveryConfig, MateSession
from repro.data import synthetic
from repro.kernels import ops
from repro.serve.clock import ManualClock
from repro.serve.engine import AsyncDiscoveryEngine

PLAN_CHILDREN = [
    "plan.init_column", "plan.hash_keys", "plan.key_ids",
    "plan.gather_candidates", "plan.profile_gate", "plan.eligibility",
]


@pytest.fixture(autouse=True)
def tracing_off():
    telemetry.disable()
    yield
    telemetry.disable()


@pytest.fixture(scope="module")
def lake():
    corpus = synthetic.make_corpus(synthetic.SyntheticSpec(n_tables=40, seed=3))
    (query, q_cols), = synthetic.make_mixed_queries(corpus, 1, 12, 2, seed=5)
    return corpus, query, q_cols


def _session(corpus):
    return MateSession.build(
        corpus, DiscoveryConfig(bits=128, k=3, window=1, backend="fused-gather")
    )


def _serve_one(session, query, q_cols):
    async def go():
        engine = AsyncDiscoveryEngine(session=session, clock=ManualClock())
        async with engine:
            req = await engine.discover_async(query, q_cols)
        return req

    return asyncio.run(go())


def test_off_records_nothing_and_costs_one_shared_noop(lake):
    corpus, query, q_cols = lake
    assert not telemetry.enabled()
    assert telemetry.span("serve.group") is telemetry.span("plan.query", rid=1)
    assert telemetry.span("x") is telemetry.NOOP
    with telemetry.span("x") as s:
        assert s is None
        telemetry.count("items", 5)
    assert telemetry.begin("serve.wait") is None
    _serve_one(_session(corpus), query, q_cols)
    telemetry.enable()
    assert telemetry.disable() == []


def _children(records):
    """Records grouped by parent id, each group in start order (compiles
    left out)."""
    kids = {}
    for s in sorted(records, key=lambda s: (s.t0_ns, s.sid)):
        if s.name != "jit.lower":
            kids.setdefault(s.parent, []).append(s)
    return kids


def test_one_request_gives_the_span_tree(lake):
    corpus, query, q_cols = lake
    session = _session(corpus)
    _serve_one(session, query, q_cols)  # compile first: lowerings stay out
    telemetry.enable()
    req = _serve_one(session, query, q_cols)
    records = telemetry.disable()
    kids = _children(records)
    roots = [s.name for s in kids[None]]
    assert roots.count("serve.pump_turn") == 1 and "serve.wait" in roots
    assert set(roots) == {"serve.pump_turn", "serve.wait"}
    (turn,) = [s for s in kids[None] if s.name == "serve.pump_turn"]
    assert turn.attrs == {"backlog": 1, "groups": 1}
    (group,) = kids[turn.sid]
    assert group.name == "serve.group"
    assert group.attrs["size"] == 1 and group.attrs["rids"] == [req.rid]
    assert group.attrs["waits"] == [0.0]  # virtual time stands still
    names = [s.name for s in kids[group.sid]]
    assert names == ["plan.query", "filter.assemble", "filter.launch", "score.request"]
    plan, _, launch, score = kids[group.sid]
    assert plan.attrs["rid"] == score.attrs["rid"] == req.rid
    assert [s.name for s in kids[plan.sid]] == PLAN_CHILDREN
    launch_kids = [s.name for s in kids[launch.sid]]
    assert launch_kids[:3] == ["filter.pad", "filter.upload", "filter.readback"]
    assert set(launch_kids) == {"filter.pad", "filter.upload", "filter.readback"}
    assert [s.name for s in kids[score.sid]] == ["score.rank", "score.tables"]
    for s in records:
        assert s.t1_ns >= s.t0_ns
        if s.parent is not None:
            parent = next(p for p in records if p.sid == s.parent)
            assert parent.t0_ns <= s.t0_ns and s.t1_ns <= parent.t1_ns
    # the sleep never parents anything: no span opened during it is its child
    waits = {s.sid for s in records if s.name == "serve.wait"}
    assert not any(s.parent in waits for s in records)


def test_empty_prunes_counter_equals_stats_delta(lake):
    """A width-3 request from different tables' columns leaves its heap
    short of k: the tables with no filter-surviving pair are pruned, and the
    ``score.tables`` span counts each of them once, as the stats do."""
    corpus, _, _ = lake
    (query, q_cols), = synthetic.make_mixed_queries(corpus, 1, 30, 3, seed=8)
    session = _session(corpus)
    _serve_one(session, query, q_cols)
    before = session.stats.tables_pruned_empty
    telemetry.enable()
    req = _serve_one(session, query, q_cols)
    records = telemetry.disable()
    (tables,) = [s.attrs for s in records if s.name == "score.tables"]
    delta = session.stats.tables_pruned_empty - before
    assert delta == req.stats.tables_pruned_empty > 0
    assert tables["tables_pruned_empty"] == delta
    assert tables["tables_pruned"] >= delta


def test_counters_equal_discovery_stats(lake):
    corpus, query, q_cols = lake
    session = _session(corpus)
    _serve_one(session, query, q_cols)
    telemetry.enable()
    req = _serve_one(session, query, q_cols)
    records = telemetry.disable()
    st = req.stats
    by = {s.name: s for s in records}
    tables = by["score.tables"].attrs
    assert tables["pairs_verified"] == st.verified_tp + st.verified_fp
    assert tables["tables_pruned"] == st.tables_pruned_rule1 + st.tables_pruned_rule2
    assert tables["tables_verified"] == st.tables_evaluated - st.tables_pruned_rule2
    assert tables["tables_pruned_empty"] == st.tables_pruned_empty
    assert tables["regather_s"] >= 0 and tables["exact_s"] > 0
    plan = by["plan.query"].attrs
    assert plan["items"] == st.pl_items_checked
    assert plan["tables"] == st.tables_fetched - st.tables_gated
    # one launch chunk: the padded operands as sent
    n = st.pl_items_checked
    q = len({tuple(row[c] for c in q_cols) for row in query.cells})
    nb, qb = ops._bucket(n, ops._FALLBACK_MIN_N), ops._pow2_bucket(q, ops._FALLBACK_MIN_Q)
    launch = by["filter.launch"].attrs
    # eligibility: one int32 id per padded item and per padded key
    assert launch["elig_bytes"] == (nb + qb) * 4
    lanes = session.index.cfg.lanes
    assert launch["h2d_bytes"] == (nb + qb) * 4 + nb * 4 + qb * lanes * 4 + nb * 4


def test_a_lowering_is_recorded_under_its_span():
    telemetry.enable()
    with telemetry.span("probe") as probe:
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(11)).block_until_ready()
    records = telemetry.disable()
    lowered = [s for s in records if s.name == "jit.lower"]
    assert lowered and all(s.parent == probe.sid for s in lowered)
    assert all(s.attrs["seconds"] >= 0 for s in lowered)


def test_count_goes_to_the_innermost_span_and_spans_nest():
    telemetry.enable()
    with telemetry.span("outer", tag="a") as outer:
        telemetry.count("n", 2)
        with telemetry.span("inner") as inner:
            telemetry.count("n")
            telemetry.count("n", 4)
        token = telemetry.begin("gap")
        telemetry.end(token, why="test")
    telemetry.count("n", 100)  # no span open: dropped
    records = telemetry.disable()
    assert outer.attrs == {"tag": "a", "n": 2} and inner.attrs == {"n": 5}
    assert inner.parent == outer.sid and outer.parent is None
    (gap,) = [s for s in records if s.name == "gap"]
    assert gap.parent == outer.sid and gap.attrs == {"why": "test"}
    assert {s.name for s in records} == {"outer", "inner", "gap"}
