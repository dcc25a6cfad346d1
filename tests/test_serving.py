"""Asyncio serving tier acceptance — deterministic fake-clock harness.

Everything here runs under virtual time (``serve.clock.ManualClock`` / a
dict-backed callable for the sync engine): arrival order, deadline expiry,
pump wake-ups and cancellation races are driven cycle-by-cycle, so the
suite is wall-clock-free and cannot flake on a loaded CI runner.

Pinned contracts (ISSUE 6):
  * backpressure — at ``max_queue`` waiting requests 'shed' REJECTS the
    future with ``AdmissionError`` (never hangs it) while 'degrade' admits
    the request at ``degrade_bits`` lane-prefix filtering with results
    still bit-identical to cold discovery (hard shed at 2×max_queue);
  * deadline-aware partial groups — ``deadline_margin`` launches a partial
    group BEFORE ``flush_after`` expires (fixed margin, or an EWMA of
    observed group service times when configured None);
  * cancellation — a cancelled future never launches and stops holding a
    window slot;
  * pump resilience — a failing group launch rejects every sibling future
    AND the background pump task keeps serving later groups;
  * caches — query-result and bound-cache hits are bit-identical to a cold
    ``discover`` at the same index state, and any §5.4 insert/update/delete
    invalidates affected entries (property-tested over random
    submit/mutate interleavings, deterministic seeds + hypothesis).
"""

import asyncio
import dataclasses
import itertools

import numpy as np
import pytest

try:  # hypothesis ships in requirements-ci.txt; the seeded property matrix
    from hypothesis import given, settings, strategies as st  # always runs

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.core import xash
from repro.core.batched import discover_many
from repro.core.corpus import Table
from repro.core.discovery import DiscoveryStats
from repro.core.index import build_index
from repro.core.session import DiscoveryConfig, MateSession, VALID_BITS
from repro.data import synthetic
from repro.serve.cache import BoundCache, QueryResultCache, query_fingerprint
from repro.serve.clock import ManualClock
from repro.serve.engine import AdmissionError, AsyncDiscoveryEngine, DiscoveryEngine


@pytest.fixture(scope="module")
def lake():
    spec = synthetic.SyntheticSpec(n_tables=60, seed=0)
    corpus = synthetic.make_corpus(spec)
    queries = synthetic.make_mixed_queries(corpus, 6, 10, 2, seed=7)
    return corpus, queries


@pytest.fixture(scope="module")
def built(lake):
    """One (corpus, queries, index) per width; mutation tests build fresh."""
    corpus, queries = lake
    return {
        bits: build_index(corpus, cfg=xash.XashConfig(bits=bits))[0]
        for bits in VALID_BITS
    }


def _fresh_index(lake, bits=128):
    corpus, _ = lake
    spec = synthetic.SyntheticSpec(n_tables=60, seed=0)
    return build_index(
        synthetic.make_corpus(spec), cfg=xash.XashConfig(bits=bits)
    )[0]


def _engine(index, clock, **cfg):
    cfg.setdefault("k", 5)
    session = MateSession(index, DiscoveryConfig(**cfg))
    return DiscoveryEngine(session=session, clock=clock), session


def _key(entries):
    return [(e.table_id, e.joinability, e.mapping) for e in entries]


def _cold(index, query, q_cols, k=5):
    # raw-engine reference at the SESSION's default flags (rank='quality' +
    # profile gate), so cache-hit comparisons stay exact including order
    return _key(
        discover_many(
            index, [(query, q_cols)], k=k, rank="quality", profile_gate=True
        )[0][0]
    )


async def _spin(n=12):
    for _ in range(n):
        await asyncio.sleep(0)


# ---------------------------------------------------------------------------
# Backpressure: shed and degrade
# ---------------------------------------------------------------------------

def test_shed_rejects_future_not_hangs(built, lake):
    _, queries = lake
    clk = ManualClock()
    eng, session = _engine(
        built[128], clk.now, window=8, max_queue=2, pressure_policy="shed"
    )
    admitted = [eng.submit(*queries[i]) for i in range(2)]
    shed = eng.submit(*queries[2])
    assert shed.future.done() and not shed.done  # rejected, NOT hung
    with pytest.raises(AdmissionError):
        shed.future.result(timeout=0)
    assert session.stats.shed == 1
    assert eng.queue == admitted  # the shed request never entered the queue
    served = eng.flush()
    assert served == admitted and all(r.done for r in admitted)


def test_degrade_admits_at_narrow_width_bit_identical(built, lake):
    """Under pressure with policy='degrade' the request is admitted at
    128-bit lane-prefix filtering: filter stats show the narrow width and
    MORE survivors, but the exact-verified top-k is bit-identical."""
    _, queries = lake
    clk = ManualClock()
    eng, session = _engine(
        built[512], clk.now, window=8, max_queue=1,
        pressure_policy="degrade", degrade_bits=128,
    )
    normal = eng.submit(*queries[0])
    degraded = eng.submit(*queries[1])  # queue at max_queue → degraded
    assert degraded.degraded and not normal.degraded
    assert session.stats.degraded == 1 and session.stats.shed == 0
    eng.flush()
    # the degraded request's group ran at 4 lanes (128 bits) of the 16-lane
    # index — the verified SET is still exactly the cold 512-bit answer.
    # (Quality ORDER may differ: the scoring head's containment term reads
    # the filter counts, and lane-prefix counts are looser by design.)
    assert degraded.stats.filter_lanes == 4
    assert sorted(_key(degraded.results)) == sorted(_cold(built[512], *queries[1]))
    assert _key(normal.results) == _cold(built[512], *queries[0])
    # degraded (prefix) filtering can only pass MORE pairs, never fewer
    cold_passed = discover_many(built[512], [queries[1]], k=5)[0][1].filter_passed
    assert degraded.stats.filter_passed >= cold_passed


def test_degrade_hard_sheds_at_twice_max_queue(built, lake):
    _, queries = lake
    clk = ManualClock()
    eng, session = _engine(
        built[256], clk.now, window=16, max_queue=1, pressure_policy="degrade"
    )
    q, qc = queries[0]
    eng.submit(q, qc)
    deg = eng.submit(q, qc)
    assert deg.degraded
    hard = eng.submit(q, qc)  # queue already at 2×max_queue
    with pytest.raises(AdmissionError):
        hard.future.result(timeout=0)
    assert session.stats.shed == 1 and session.stats.degraded == 1


def test_unbounded_queue_never_sheds(built, lake):
    _, queries = lake
    clk = ManualClock()
    eng, session = _engine(built[128], clk.now, window=4)  # max_queue=None
    reqs = [eng.submit(*queries[i % len(queries)]) for i in range(20)]
    assert session.stats.shed == 0 and len(eng.queue) == 20
    eng.flush()
    assert all(r.done for r in reqs)


# ---------------------------------------------------------------------------
# Deadline-aware partial-group launch
# ---------------------------------------------------------------------------

def test_fixed_margin_launches_partial_group_early(built, lake):
    _, queries = lake
    clk = ManualClock()
    eng, _ = _engine(
        built[128], clk.now, window=8, flush_after=1.0, deadline_margin=0.25
    )
    r1 = eng.submit(*queries[0])
    assert eng.next_deadline() == pytest.approx(0.75)
    clk.advance(0.74)
    assert eng.pump() == []  # margin-adjusted deadline not reached
    clk.advance(0.01)
    assert eng.pump() == [r1]  # launched 0.25 BEFORE flush_after expires


def test_margin_preserves_arrival_order_across_groups(built, lake):
    """Deadlines derive from each group's OLDEST request: with a margin the
    first partial group launches early and the later submit launches in its
    own (later) group — ordering by arrival, never inverted."""
    _, queries = lake
    clk = ManualClock()
    eng, _ = _engine(
        built[128], clk.now, window=2, flush_after=1.0, deadline_margin=0.5
    )
    r1 = eng.submit(*queries[0])
    clk.advance(0.6)  # r1's margin-adjusted deadline (0.5) already passed
    r2 = eng.submit(*queries[1])
    served = eng.pump()
    # both were queued → window of 2 filled → one group, submission order
    assert served == [r1, r2]
    r3 = eng.submit(*queries[2])
    assert eng.pump() == []
    assert eng.next_deadline() == pytest.approx(0.6 + 0.5)
    clk.advance(0.5)
    assert eng.pump() == [r3]


def test_auto_margin_tracks_observed_service_time(built, lake):
    """deadline_margin=None: the engine learns the margin from an EWMA of
    group service times, measured on the injected clock.  The ticking clock
    advances 0.01 per read, and ``_serve_group`` reads it exactly twice
    (start/end), so every observed service time is exactly 0.01."""
    _, queries = lake
    t = {"now": 0.0}

    def ticking_clock():
        t["now"] += 0.01
        return t["now"]

    eng, _ = _engine(
        built[128], ticking_clock, window=4, flush_after=10.0,
        deadline_margin=None,
    )
    assert eng._margin() == 0.0  # nothing observed yet
    eng.submit(*queries[0])
    eng.flush()
    assert eng._margin() == pytest.approx(0.01)
    assert eng._margin() == eng._service_ewma
    # the learned margin moves next_deadline earlier than flush_after
    r = eng.submit(*queries[1])
    assert eng.next_deadline() == pytest.approx(r.arrival + 10.0 - 0.01)
    eng.flush()
    # EWMA of a constant signal stays put: 0.7*m + 0.3*0.01 == 0.01
    assert eng._margin() == pytest.approx(0.01)


# ---------------------------------------------------------------------------
# Cancellation
# ---------------------------------------------------------------------------

def test_cancelled_request_never_launches_and_frees_window(built, lake):
    _, queries = lake
    clk = ManualClock()
    eng, _ = _engine(built[128], clk.now, window=2, flush_after=None)
    r1 = eng.submit(*queries[0])
    r2 = eng.submit(*queries[1])
    assert r2.cancel() and r2.cancelled
    served = eng.pump()  # r2 purged → window of 2 no longer full
    assert served == [] and eng.queue == [r1]
    r3 = eng.submit(*queries[2])
    served = eng.pump()  # r1 + r3 fill the window; r2 never launches
    assert served == [r1, r3]
    assert r2.results is None and r2.future.cancelled()


def test_cancelled_mid_queue_flush_skips_it(built, lake):
    _, queries = lake
    clk = ManualClock()
    eng, session = _engine(built[128], clk.now, window=2, flush_after=None)
    reqs = [eng.submit(*queries[i]) for i in range(4)]
    reqs[1].cancel()
    reqs[3].cancel()
    served = eng.flush()
    assert served == [reqs[0], reqs[2]]
    assert session.stats.requests == 2  # cancelled requests cost nothing
    assert all(r.future.cancelled() for r in (reqs[1], reqs[3]))


# ---------------------------------------------------------------------------
# Async pump task: interleaving, failure resilience, lifecycle
# ---------------------------------------------------------------------------

def test_async_pump_serves_window_and_deadline_groups(built, lake):
    _, queries = lake

    async def run():
        clk = ManualClock()
        session = MateSession(
            built[128], DiscoveryConfig(k=5, window=2, flush_after=1.0)
        )
        async with AsyncDiscoveryEngine(session=session, clock=clk) as eng:
            # window path: two submits fill the group, no clock advance
            a = asyncio.ensure_future(eng.discover_async(*queries[0]))
            b = asyncio.ensure_future(eng.discover_async(*queries[1]))
            await asyncio.gather(a, b)
            # deadline path: a single straggler waits for virtual time
            c = asyncio.ensure_future(eng.discover_async(*queries[2]))
            await _spin()
            assert not c.done()  # partial group, deadline not reached
            clk.advance(1.0)
            rc = await c
            assert rc.done
        for task, (q, qc) in zip((a, b, c), queries[:3]):
            assert _key(task.result().results) == _cold(built[128], q, qc)

    asyncio.run(run())


def test_async_group_failure_rejects_siblings_and_pump_survives(built, lake):
    """Satellite fix: a failed group launch inside the BACKGROUND pump task
    must reject every sibling future and keep the pump alive for later
    groups (an uncaught exception would orphan the loop)."""
    _, queries = lake

    async def run():
        clk = ManualClock()
        session = MateSession(
            built[128], DiscoveryConfig(k=5, window=2, flush_after=None)
        )
        async with AsyncDiscoveryEngine(session=session, clock=clk) as eng:
            good_sib = asyncio.ensure_future(eng.discover_async(*queries[0]))
            bad = asyncio.ensure_future(
                eng.discover_async(queries[0][0], [99])  # IndexError in plan
            )
            with pytest.raises(IndexError):
                await bad
            with pytest.raises(IndexError):
                await good_sib  # sibling rejected, not hung
            assert eng.pump_errors == 1
            assert eng._task is not None and not eng._task.done()  # alive
            # the pump keeps serving fresh groups after the failure
            ra, rb = await asyncio.gather(
                eng.discover_async(*queries[1]), eng.discover_async(*queries[2])
            )
            assert ra.done and rb.done
            assert eng.pump_errors == 1

    asyncio.run(run())


def test_async_cancelled_futures_never_launch(built, lake):
    _, queries = lake

    async def run():
        clk = ManualClock()
        session = MateSession(
            built[128], DiscoveryConfig(k=5, window=2, flush_after=5.0)
        )
        async with AsyncDiscoveryEngine(session=session, clock=clk) as eng:
            doomed = eng.submit(*queries[0])
            await _spin()
            doomed.cancel()
            served_before = session.stats.requests
            a, b = await asyncio.gather(
                eng.discover_async(*queries[1]), eng.discover_async(*queries[2])
            )
            assert a.done and b.done
            assert doomed.results is None and doomed.future.cancelled()
            assert session.stats.requests == served_before + 2

    asyncio.run(run())


def test_async_stop_drain_false_rejects_backlog(built, lake):
    _, queries = lake

    async def run():
        clk = ManualClock()
        session = MateSession(
            built[128], DiscoveryConfig(k=5, window=8, flush_after=None)
        )
        eng = AsyncDiscoveryEngine(session=session, clock=clk)
        await eng.start()
        req = eng.submit(*queries[0])  # partial group, no deadline: waits
        await _spin()
        await eng.stop(drain=False)
        with pytest.raises(AdmissionError):
            req.future.result(timeout=0)
        assert eng.queue == []

    asyncio.run(run())


def test_sync_discover_async_waiters_interleave_with_caches(built, lake):
    """The self-pumping sync waiters (no background task) still compose
    with the caches: one cold group, then hits resolve at submit."""
    _, queries = lake
    session = MateSession(
        built[128],
        DiscoveryConfig(k=5, window=4, flush_after=0.01, result_cache=8),
    )
    eng = DiscoveryEngine(session=session)

    async def run():
        first = await asyncio.gather(
            *[eng.discover_async(q, qc) for q, qc in queries[:3]]
        )
        again = await asyncio.gather(
            *[eng.discover_async(q, qc) for q, qc in queries[:3]]
        )
        return first, again

    first, again = asyncio.run(run())
    assert session.stats.cache_hits == 3
    for r1, r2 in zip(first, again):
        assert r2.from_cache and _key(r1.results) == _key(r2.results)


# ---------------------------------------------------------------------------
# Caches: unit behaviour
# ---------------------------------------------------------------------------

def test_fingerprint_is_content_keyed(lake):
    _, queries = lake
    (q, qc) = queries[0]
    # identity (table_id, name) is irrelevant — content decides
    clone = dataclasses.replace(q, table_id=999, name="other")
    assert query_fingerprint(q, qc) == query_fingerprint(clone, qc)
    assert query_fingerprint(q, qc) != query_fingerprint(q, list(reversed(qc)))
    assert query_fingerprint(q, qc, "order") != query_fingerprint(q, qc, "tls")
    # framing: value-boundary shifts must not collide
    t1 = Table(0, [["ab", "c"]])
    t2 = Table(0, [["a", "bc"]])
    assert query_fingerprint(t1, [0, 1]) != query_fingerprint(t2, [0, 1])


def test_result_cache_lru_eviction_and_stats():
    cache = QueryResultCache(2)
    cache.put(b"a", 5, 0, [], DiscoveryStats())
    cache.put(b"b", 5, 0, [], DiscoveryStats())
    assert cache.get(b"a", 5, 0) is not None  # refreshes a's recency
    cache.put(b"c", 5, 0, [], DiscoveryStats())  # evicts b (LRU)
    assert cache.get(b"b", 5, 0) is None
    assert cache.get(b"a", 5, 0) is not None
    assert cache.stats.evictions == 1 and cache.stats.hits == 2
    # same fingerprint, different k: distinct entries
    assert cache.get(b"a", 3, 0) is None
    assert cache.stats.hit_rate == pytest.approx(2 / 4)


def test_caches_drop_stale_epoch_entries():
    cache = QueryResultCache(4)
    cache.put(b"x", 5, 7, [], DiscoveryStats())
    assert cache.get(b"x", 5, 7) is not None
    assert cache.get(b"x", 5, 8) is None  # epoch moved: dropped, counted
    assert cache.stats.stale == 1
    assert len(cache) == 0  # the stale entry was evicted, not kept


def test_cache_capacity_validation():
    with pytest.raises(ValueError):
        QueryResultCache(0)
    with pytest.raises(ValueError):
        BoundCache(-1)


def test_config_validates_serving_knobs():
    for bad in (
        dict(max_queue=0),
        dict(pressure_policy="drop"),
        dict(degrade_bits=64),
        dict(deadline_margin=-1.0),
        dict(result_cache=-1),
        dict(bound_cache=-1),
    ):
        with pytest.raises(ValueError):
            DiscoveryConfig(**bad)
    # None means auto/disabled, not invalid
    DiscoveryConfig(max_queue=None, deadline_margin=None)


# ---------------------------------------------------------------------------
# Caches: engine integration + §5.4 invalidation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", VALID_BITS)
def test_result_cache_hit_bit_identical_all_widths(lake, bits):
    _, queries = lake
    index = _fresh_index(lake, bits)
    clk = ManualClock()
    eng, session = _engine(
        index, clk.now, window=4, flush_after=None, result_cache=8, bound_cache=8
    )
    q, qc = queries[0]
    cold_req = eng.discover(q, qc)
    hit_req = eng.discover(q, qc)
    assert hit_req.from_cache and session.stats.cache_hits == 1
    assert _key(hit_req.results) == _key(cold_req.results) == _cold(index, q, qc)
    # a hit never touches the queue or the filter
    assert hit_req.stats.filter_checks == cold_req.stats.filter_checks


@pytest.mark.parametrize("mutation", ["insert", "update", "delete"])
def test_mutation_invalidates_cached_results(lake, mutation):
    """§5.4 mutations must drop affected cache entries — the post-mutation
    answer is re-discovered, never replayed stale."""
    _, queries = lake
    index = _fresh_index(lake, 128)
    clk = ManualClock()
    eng, session = _engine(
        index, clk.now, window=4, flush_after=None, result_cache=8, bound_cache=8
    )
    q, qc = queries[0]
    first = eng.discover(q, qc)
    assert eng.discover(q, qc).from_cache  # warm before the mutation
    top = first.results[0].table_id if first.results else 0
    if mutation == "insert":
        # insert a copy of the query's own key columns: a new perfect join
        # candidate that MUST surface in the fresh answer
        session.insert_table([[r[c] for c in qc] for r in q.cells])
    elif mutation == "update":
        session.update_cell(top, 0, 0, "mutated-value-xyz")
    else:
        session.delete_table(top)
    after = eng.discover(q, qc)
    assert not after.from_cache  # stale entry was invalidated
    assert _key(after.results) == _cold(index, q, qc)  # fresh ground truth
    if mutation == "delete":
        assert all(e.table_id != top for e in after.results)


def test_bound_cache_serves_any_k_and_skips_filter(lake):
    _, queries = lake
    index = _fresh_index(lake, 128)
    clk = ManualClock()
    eng, session = _engine(
        index, clk.now, window=4, flush_after=None, bound_cache=8
    )
    q, qc = queries[0]
    eng.discover(q, qc, k=5)
    checks_after_cold = session.stats.filter_checks
    fused_after_cold = session.stats.filter_fused_launches
    matrix_after_cold = session.stats.filter_matrix_bytes
    warm = eng.discover(q, qc, k=3)  # different k: result cache can't help
    assert session.stats.bound_hits == 1
    # phase A (gather + filter launch) was skipped entirely
    assert session.stats.filter_fused_launches == fused_after_cold
    assert session.stats.filter_matrix_bytes == matrix_after_cold
    assert session.stats.filter_checks == checks_after_cold + warm.stats.filter_checks
    assert _key(warm.results) == _cold(index, q, qc, k=3)


# ---------------------------------------------------------------------------
# Property: random submit/mutate interleavings — hits bit-identical, no
# stale top-k.  Seeded versions ALWAYS run; hypothesis widens the net in CI.
# ---------------------------------------------------------------------------

_MUTATIONS = ("insert", "update", "delete", "none")


def _run_interleaving(bits: int, ops: list[tuple[str, int]]) -> None:
    """Drive an engine with caches through a submit/mutate schedule; after
    EVERY serve, the result must equal a cold discover on the CURRENT index
    (catches both stale cache hits and missed invalidations)."""
    spec = synthetic.SyntheticSpec(n_tables=24, rows_per_table=(4, 10), seed=3)
    corpus = synthetic.make_corpus(spec)
    queries = synthetic.make_mixed_queries(corpus, 4, 6, 2, seed=11)
    index = build_index(corpus, cfg=xash.XashConfig(bits=bits))[0]
    clk = ManualClock()
    session = MateSession(
        index,
        DiscoveryConfig(
            k=4, window=3, flush_after=None, result_cache=4, bound_cache=4
        ),
    )
    eng = DiscoveryEngine(session=session, clock=clk.now)
    live_tables = list(range(len(corpus.tables)))
    pending = []
    epochs_seen = {index.mutation_epoch}
    for op, arg in ops:
        if op == "submit":
            q, qc = queries[arg % len(queries)]
            req = eng.submit(q, qc, k=4)
            if req.done:
                # result-cache hit: answered AT SUBMIT, so it must equal a
                # cold discover against the index as it is RIGHT NOW (a
                # later mutation legitimately changes later answers).
                assert req.from_cache
                assert _key(req.results) == _cold(index, q, qc, k=4)
            else:
                pending.append((req, q, qc))
        elif op == "flush":
            eng.flush()
            for req, q, qc in pending:
                assert req.done
                # THE property: whatever path served it (cold, result-cache
                # hit, bound-cache replay), the answer equals a cold
                # discover against the index AS IT IS NOW.
                assert _key(req.results) == _cold(index, q, qc, k=4), (
                    f"served result diverged from cold discover (op schedule "
                    f"{ops}, from_cache={req.from_cache})"
                )
            pending.clear()
        elif op == "insert" and arg % 2 == 0:
            q, qc = queries[arg % len(queries)]
            tid = session.insert_table([[r[c] for c in qc] for r in q.cells])
            live_tables.append(tid)
        elif op == "insert":
            tid = session.insert_table([["zz", str(arg)], ["yy", "ww"]])
            live_tables.append(tid)
        elif op == "update" and live_tables:
            session.update_cell(live_tables[arg % len(live_tables)], 0, 0, f"v{arg}")
        elif op == "delete" and live_tables:
            session.delete_table(live_tables.pop(arg % len(live_tables)))
        epochs_seen.add(index.mutation_epoch)
    eng.flush()
    for req, q, qc in pending:
        assert _key(req.results) == _cold(index, q, qc, k=4)
    # sanity: schedules with mutations actually moved the epoch
    if any(op in ("insert", "update", "delete") for op, _ in ops):
        assert len(epochs_seen) > 1


def _schedule_from_seed(seed: int, n_ops: int = 14) -> list[tuple[str, int]]:
    rng = np.random.default_rng(seed)
    ops = []
    for _ in range(n_ops):
        roll = rng.random()
        if roll < 0.45:
            ops.append(("submit", int(rng.integers(0, 8))))
        elif roll < 0.65:
            ops.append(("flush", 0))
        elif roll < 0.77:
            ops.append(("insert", int(rng.integers(0, 8))))
        elif roll < 0.89:
            ops.append(("update", int(rng.integers(0, 8))))
        else:
            ops.append(("delete", int(rng.integers(0, 8))))
    ops.append(("flush", 0))
    return ops


@pytest.mark.parametrize("bits", VALID_BITS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_interleaving_property_seeded(bits, seed):
    """Deterministic always-run slice of the property: random (seeded)
    submit/mutate interleavings never serve a result that differs from a
    cold discover at serve time — at every hash width."""
    _run_interleaving(bits, _schedule_from_seed(seed * 31 + bits))


if HAVE_HYPOTHESIS:
    op_strat = st.one_of(
        st.tuples(st.just("submit"), st.integers(0, 7)),
        st.tuples(st.just("flush"), st.just(0)),
        st.tuples(st.just("insert"), st.integers(0, 7)),
        st.tuples(st.just("update"), st.integers(0, 7)),
        st.tuples(st.just("delete"), st.integers(0, 7)),
    )

    @settings(max_examples=12, deadline=None)
    @given(ops=st.lists(op_strat, min_size=2, max_size=12))
    def test_interleaving_property_hypothesis(ops):
        """Arbitrary submit/mutate interleavings ⇒ every cache hit is
        bit-identical to a cold discover and no §5.4 mutation leaves a
        stale entry servable (hypothesis-driven; 128-bit for speed — the
        seeded matrix covers all widths)."""
        _run_interleaving(128, list(ops) + [("flush", 0)])

    @settings(max_examples=10, deadline=None)
    @given(
        k1=st.integers(1, 6),
        k2=st.integers(1, 6),
        qi=st.integers(0, 3),
    )
    def test_bound_cache_any_k_hypothesis(k1, k2, qi):
        """A bound-cache replay at ANY k equals the cold discover at that
        k (phase-B scoring is k-independent of the cached phase A)."""
        spec = synthetic.SyntheticSpec(n_tables=24, rows_per_table=(4, 10), seed=3)
        corpus = synthetic.make_corpus(spec)
        queries = synthetic.make_mixed_queries(corpus, 4, 6, 2, seed=11)
        index = build_index(corpus, cfg=xash.XashConfig(bits=128))[0]
        clk = ManualClock()
        session = MateSession(
            index, DiscoveryConfig(k=4, window=2, flush_after=None, bound_cache=4)
        )
        eng = DiscoveryEngine(session=session, clock=clk.now)
        q, qc = queries[qi]
        eng.discover(q, qc, k=k1)
        warm = eng.discover(q, qc, k=k2)
        assert session.stats.bound_hits == 1
        assert _key(warm.results) == _cold(index, q, qc, k=k2)


# ---------------------------------------------------------------------------
# ISSUE 10: degrade × profile-gate × cache hygiene, and the FD-workload
# fingerprint split
# ---------------------------------------------------------------------------

def test_degraded_gated_request_exact_and_never_poisons_bound_cache(built, lake):
    """A degraded (128-bit lane-prefix) admission with the profile gate ON
    must still verify to exactly the cold full-width gated answer, and its
    phase-A bounds must NEVER enter the BoundCache (they are looser by
    design: a hot entry would keep replaying the wide survivor set long
    after the pressure spike ended).  The exact post-verification RESULTS
    may be cached — a replay is bit-identical."""
    _, queries = lake
    clk = ManualClock()
    eng, session = _engine(
        built[512], clk.now, window=8, max_queue=1,
        pressure_policy="degrade", degrade_bits=128,
        result_cache=8, bound_cache=8,
    )
    normal = eng.submit(*queries[0])
    degraded = eng.submit(*queries[1])  # queue at max_queue → degraded
    assert degraded.degraded and not normal.degraded
    eng.flush()
    epoch = built[512].mutation_epoch
    # gate on (session default) + 4-lane prefix filtering: the verified SET
    # is still exactly the cold 512-bit gated answer (order may differ —
    # the quality score's containment term reads the looser prefix counts)
    assert degraded.stats.filter_lanes == 4
    assert sorted(_key(degraded.results)) == sorted(_cold(built[512], *queries[1]))
    # bound-cache hygiene: the full-width request's bounds were cached, the
    # degraded request's were not
    assert eng.bound_cache.get(normal.fingerprint, epoch) is not None
    assert eng.bound_cache.get(degraded.fingerprint, epoch) is None
    # the RESULT cache did keep the degraded answer — it is exact after
    # verification, so a replay must be bit-identical to the cold answer
    hit = eng.submit(*queries[1])
    assert hit.from_cache and session.stats.cache_hits == 1
    assert sorted(_key(hit.results)) == sorted(_cold(built[512], *queries[1]))
    # ... and the replay resolves at submit: no queue slot, no filter work
    assert hit not in eng.queue


def test_fd_workload_fingerprint_never_hits_join_caches(built, lake):
    """FD validation re-uses plan_and_count, so an FD request's fingerprint
    MUST differ from the join-workload fingerprint of the same query —
    otherwise an FD pass could replay a cached join result (or vice versa).
    The ``workload`` field pins the split; the default stays 'join' so
    every pre-FD digest is unchanged."""
    _, queries = lake
    q, qc = queries[0]
    cfg = DiscoveryConfig()
    join_fp = query_fingerprint(
        q, qc, cfg.init_mode, rank=cfg.rank, profile_gate=cfg.profile_gate
    )
    # default == explicit workload='join' (pre-FD digests unchanged)
    assert join_fp == query_fingerprint(
        q, qc, cfg.init_mode, rank=cfg.rank, profile_gate=cfg.profile_gate,
        workload="join",
    )
    # distinct workloads → distinct digests; FD callers encode the dependent
    # column and min_support so different FD targets never collide either
    fd_fp = query_fingerprint(
        q, qc, cfg.init_mode, rank=cfg.rank, profile_gate=cfg.profile_gate,
        workload="fd:2:1",
    )
    assert fd_fp != join_fp
    assert fd_fp != query_fingerprint(
        q, qc, cfg.init_mode, rank=cfg.rank, profile_gate=cfg.profile_gate,
        workload="fd:3:1",
    )
    # engine integration: warm the join result cache, then assert the FD
    # fingerprint misses both caches at every k
    clk = ManualClock()
    eng, session = _engine(
        built[128], clk.now, window=4, flush_after=None,
        result_cache=8, bound_cache=8,
    )
    cold = eng.discover(q, qc)
    assert eng.discover(q, qc).from_cache  # join entry is hot
    epoch = built[128].mutation_epoch
    assert eng.result_cache.get(cold.fingerprint, cold.k, epoch) is not None
    assert eng.result_cache.get(fd_fp, cold.k, epoch) is None
    assert eng.bound_cache.get(fd_fp, epoch) is None
