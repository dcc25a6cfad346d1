"""MateSession / DiscoveryConfig / async DiscoveryEngine acceptance.

The redesign's contract (ISSUE 4, amended by ISSUE 9): the session's
verified top-k SET is bit-identical to the pre-redesign entry points across
widths 128/256/512 and all backends (numpy/xla/pallas/fused) — since ISSUE 9
the session defaults to ``rank='quality'``, which REORDERS that set by the
scoring head (and the profile gate prunes candidates without changing it),
so set-level comparisons run against the count-ranked scalar engine and
order checks against the scoring head's own annotations.  ``discover`` runs
the serving tier's two phases on a group of one.  The engine's arrival-window batching honours window-full
and flush-after-deadline semantics deterministically.  The PR 4 deprecation
shims (``use_kernel=``/``fused=``/``impl=``) were REMOVED one release later
(ISSUE 5): the old kwargs now raise TypeError — pinned below.
"""

import asyncio
import dataclasses

import numpy as np
import pytest

from repro.core import discovery, xash
from repro.core import batched
from repro.core.batched import discover_many
from repro.core.index import MateIndex
from repro.core.session import DiscoveryConfig, MateSession, VALID_BITS
from repro.data import synthetic
from repro.serve.engine import DiscoveryEngine
from repro.kernels import registry
from repro.kernels.registry import Backend

BACKENDS = ("numpy", "xla", "pallas", "fused", "fused-gather")


@pytest.fixture(scope="module")
def lake():
    spec = synthetic.SyntheticSpec(n_tables=120, seed=0)
    corpus = synthetic.make_corpus(spec)
    query, q_cols, _expected, corpus = synthetic.make_query_with_ground_truth(corpus)
    return corpus, query, q_cols


@pytest.fixture(scope="module")
def sessions(lake):
    """One session per width (index builds are the expensive part)."""
    corpus, _q, _qc = lake
    return {
        bits: MateSession.build(corpus, DiscoveryConfig(bits=bits))
        for bits in VALID_BITS
    }


def _key(entries):
    return [(e.table_id, e.joinability, e.mapping) for e in entries]


def _same_set(a, b):
    """Rank-mode-agnostic comparison: the verified top-k SET (ids, scores,
    mappings) must match; order is the rank mode's business."""
    return sorted(_key(a)) == sorted(_key(b))


# ---------------------------------------------------------------------------
# DiscoveryConfig
# ---------------------------------------------------------------------------

def test_config_is_frozen_and_hashable():
    cfg = DiscoveryConfig(backend="fused", bits=256)
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.k = 3
    assert hash(cfg) == hash(DiscoveryConfig(backend="fused", bits=256))


@pytest.mark.parametrize("kw", [
    {"bits": 96},
    {"backend": "cuda"},
    {"fused_block_n": 100},
    {"fused_block_n": 384},
    {"window": 0},
    {"k": 0},
    {"flush_after": -1.0},
])
def test_config_validation(kw):
    with pytest.raises(ValueError):
        DiscoveryConfig(**kw)


def test_config_resolves_backend(monkeypatch):
    assert DiscoveryConfig(backend="numpy").resolve_backend().name == "numpy"
    monkeypatch.setenv("MATE_FILTER_BACKEND", "xla")
    # config level beats env; unset config follows env
    assert DiscoveryConfig(backend="fused").resolve_backend().name == "fused"
    assert DiscoveryConfig().resolve_backend().name == "xla"


def test_session_adopts_index_ground_truth(lake):
    corpus, _q, _qc = lake
    index = MateIndex(corpus, cfg=xash.XashConfig(bits=256))
    session = MateSession(index, DiscoveryConfig(bits=128))
    assert session.bits == 256 and session.config.bits == 256


def test_session_build_records_build_stats(sessions):
    """MateSession.build carries the offline-phase BuildStats; wrapping an
    externally built index does not invent one."""
    s = sessions[128]
    assert s.build_stats is not None
    assert s.build_stats.n_shards == 1 and not s.build_stats.sharded
    assert s.build_stats.values_total == len(s.index.corpus.unique_values)
    assert s.build_stats.bytes_hashed == s.index.corpus.unique_enc.size
    assert s.build_stats.total_seconds > 0
    assert MateSession(s.index).build_stats is None


# ---------------------------------------------------------------------------
# Acceptance: bit-identity across widths × backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", VALID_BITS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_session_discover_bit_identical(sessions, lake, bits, backend):
    """session.discover returns the scalar Algorithm 1 SET, ordered by the
    quality annotations of its scoring head, at every width and backend."""
    _corpus, query, q_cols = lake
    base = sessions[bits]
    session = MateSession(
        base.index, dataclasses.replace(base.config, backend=backend, k=10)
    )
    ref, _ = discovery.discover(session.index, query, q_cols, k=10)
    got, stats = session.discover(query, q_cols)
    assert _same_set(got, ref)
    assert all(e.quality is not None for e in got)
    assert _key(got) == _key(
        sorted(got, key=lambda e: (-e.quality, -e.joinability, e.table_id))
    )
    if backend in ("fused", "fused-gather"):
        assert stats.filter_matrix_bytes == 0
        assert stats.filter_fused_launches > 0
    if backend == "fused-gather":
        # the host never gathered the candidate superkeys: every launch
        # saved n × (lanes·4 − 4) bytes of gather traffic
        assert stats.gather_bytes_saved > 0
    else:
        assert stats.gather_bytes_saved == 0


@pytest.mark.parametrize("backend", BACKENDS)
def test_session_discover_many_bit_identical(sessions, lake, backend):
    corpus, query, q_cols = lake
    base = sessions[128]
    session = MateSession(
        base.index, dataclasses.replace(base.config, backend=backend)
    )
    queries = [(query, q_cols)] + synthetic.make_mixed_queries(
        corpus, 2, 12, 2, seed=21
    )
    out = session.discover_many(queries, k=[10, 3, 5])
    for (q, qc), k_i, (entries, _st) in zip(queries, [10, 3, 5], out):
        ref, _ = discovery.discover(session.index, q, qc, k=k_i)
        assert _same_set(entries, ref)


@pytest.mark.parametrize("backend", ["numpy", "auto", "fused", "fused-gather"])
def test_session_discover_is_the_served_path(sessions, lake, backend):
    """MateSession.discover and the serving tier at a window of one run the
    same two phases: the same request gets identical entries (order,
    scores, mappings, quality) and identical DiscoveryStats either way, and
    the two sessions aggregate identically."""
    corpus, query, q_cols = lake
    base = sessions[128]
    config = dataclasses.replace(base.config, backend=backend, k=10)
    direct = MateSession(base.index, config)
    served = MateSession(base.index, config)
    engine = DiscoveryEngine(served, batch=1)
    queries = [(query, q_cols)] + synthetic.make_mixed_queries(
        corpus, 2, 12, 2, seed=21
    )
    for q, qc in queries:
        got, stats = direct.discover(q, qc)
        req = engine.discover(q, qc)
        assert got == req.results  # TopKEntry fields, quality included
        assert stats == req.stats
    assert direct.stats == served.stats
    assert direct.stats.requests == len(queries)


@pytest.mark.parametrize("backend", registry.backend_names())
def test_plan_counts_hits_are_host_or_none(sessions, lake, backend):
    """Phase A hands phase B either no match matrix (the counts-only fused
    and routed launches) or a host numpy slice of it — never a device
    array — so scoring has exactly those two cases to handle."""
    corpus, query, q_cols = lake
    index = sessions[128].index
    queries = [(query, q_cols)] + synthetic.make_mixed_queries(
        corpus, 2, 12, 2, seed=21
    )
    pcs = batched.plan_and_count(index, queries, backend)
    assert len(pcs) == len(queries)
    for pc in pcs:
        assert pc.hits is None or isinstance(pc.hits, np.ndarray)
        assert (pc.hits is None) == pc.fused
        if pc.hits is not None:
            assert pc.hits.dtype == bool
            # the plan's own block of the group matrix
            assert pc.hits.shape == pc.plan.elig.dense().shape
        assert pc.counts.shape == (pc.plan.block.n_tables,)


def test_session_stats_accumulate(sessions, lake):
    _corpus, query, q_cols = lake
    session = MateSession(sessions[128].index, DiscoveryConfig(k=5))
    assert session.stats.requests == 0
    session.discover(query, q_cols)
    session.discover_many([(query, q_cols)] * 2)
    assert session.stats.requests == 3
    assert session.stats.filter_checks > 0
    assert 0.0 <= session.stats.precision <= 1.0


def test_ops_fused_block_n_rejects_bad_override():
    """The ops-level override check is a ValueError with the same wording as
    DiscoveryConfig.__post_init__ — it used to be a bare assert, which a
    ``python -O`` run silently skipped, letting a non-pow2 block reach the
    kernel."""
    from repro.kernels import ops

    row = np.zeros((4, 4), dtype=np.uint32)
    qk = np.zeros((2, 4), dtype=np.uint32)
    seg = np.zeros(4, dtype=np.int32)
    for bad in (100, 384, 64):
        with pytest.raises(
            ValueError,
            match=rf"fused_block_n must be a power of two >= 128, got {bad}",
        ):
            ops.filter_table_counts(row, qk, None, seg, 2, block_n=bad)
        with pytest.raises(ValueError, match="power of two >= 128"):
            ops.gather_filter_table_counts(
                jnp_store(), np.zeros(4, np.int64), qk, None, seg, 2,
                block_n=bad,
            )


def jnp_store():
    import jax.numpy as jnp

    return jnp.zeros((8, 4), dtype=jnp.uint32)


def test_ops_fused_block_n_validates_under_python_O():
    """Regression for the bare-assert bug: the check must still fire with
    assertions compiled out (``python -O``)."""
    import os
    import subprocess
    import sys

    script = (
        "import numpy as np\n"
        "from repro.kernels import ops\n"
        "row = np.zeros((4, 4), dtype=np.uint32)\n"
        "qk = np.zeros((2, 4), dtype=np.uint32)\n"
        "seg = np.zeros(4, dtype=np.int32)\n"
        "try:\n"
        "    ops.filter_table_counts(row, qk, None, seg, 2, block_n=100)\n"
        "except ValueError as e:\n"
        "    ok = 'fused_block_n must be a power of two >= 128, got 100' in str(e)\n"
        "    print('OK' if ok else 'WRONG-MESSAGE:' + str(e))\n"
        "else:\n"
        "    print('NO-ERROR')\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
    )
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-O", "-c", script],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "OK", (proc.stdout, proc.stderr)


def test_session_fused_block_n_override(sessions, lake):
    """A config-pinned fused row block changes tiling only, never results."""
    _corpus, query, q_cols = lake
    base = sessions[128]
    ref, _ = base.discover(query, q_cols, k=10)
    session = MateSession(
        base.index,
        DiscoveryConfig(backend="fused", fused_block_n=128, k=10),
    )
    got, stats = session.discover(query, q_cols)
    assert _key(got) == _key(ref)
    assert stats.filter_matrix_bytes == 0


# ---------------------------------------------------------------------------
# Deprecation REMOVAL: the PR 4 shims are gone — old kwargs raise TypeError
# ---------------------------------------------------------------------------

def test_removed_use_kernel_kwarg_raises(sessions, lake):
    _corpus, query, q_cols = lake
    index = sessions[128].index
    with pytest.raises(TypeError, match="use_kernel"):
        discover_many(index, [(query, q_cols)], k=10, use_kernel=False)
    # the modern spelling of the old flag
    got, _ = discover_many(index, [(query, q_cols)], k=10, backend="numpy")[0]
    ref, _ = discovery.discover(index, query, q_cols, k=10)
    assert _key(got) == _key(ref)


def test_removed_fused_kwarg_raises(sessions, lake):
    _corpus, query, q_cols = lake
    index = sessions[128].index
    with pytest.raises(TypeError, match="fused"):
        discover_many(index, [(query, q_cols)], k=[5], fused=True)
    with pytest.raises(TypeError, match="fused"):
        discover_many(index, [(query, q_cols)], k=10, fused=False)
    with pytest.raises(TypeError, match="fused"):
        DiscoveryEngine(index, batch=2, fused=True)
    with pytest.raises(TypeError, match="use_kernel"):
        DiscoveryEngine(index, batch=2, use_kernel=False)


def test_removed_distributed_impl_kwarg_raises(sessions, lake):
    from repro.core import distributed
    import jax

    corpus, _query, _q_cols = lake
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    with pytest.raises(TypeError, match="impl"):
        distributed.make_distributed_filter(
            mesh, len(corpus.tables), ("data",), impl="blocked"
        )


def test_resolve_engine_backend_shim_is_gone():
    """The legacy-flag translation layer itself was deleted with the shims —
    backend resolution is kernels.registry only."""
    from repro.core import batched

    assert not hasattr(batched, "resolve_engine_backend")
    assert not hasattr(batched, "_UNSET")


# ---------------------------------------------------------------------------
# Async engine: window / deadline semantics
# ---------------------------------------------------------------------------

def _engine(session_base, queries, window=2, flush_after=1.0):
    clock = {"t": 0.0}
    session = MateSession(
        session_base.index,
        DiscoveryConfig(window=window, flush_after=flush_after, k=5),
    )
    eng = DiscoveryEngine(session=session, clock=lambda: clock["t"])
    return eng, clock


def test_engine_window_fills_before_deadline(sessions, lake):
    corpus, query, q_cols = lake
    queries = [(query, q_cols)] + synthetic.make_mixed_queries(
        corpus, 2, 10, 2, seed=31
    )
    eng, clock = _engine(sessions[128], queries, window=2, flush_after=10.0)
    r1 = eng.submit(*queries[0])
    assert eng.pump() == []  # neither window nor deadline
    r2 = eng.submit(*queries[1])
    served = eng.pump()  # window of 2 filled — deadline irrelevant
    assert served == [r1, r2] and r1.done and r2.done


def test_engine_deadline_flushes_partial_group(sessions, lake):
    _corpus, query, q_cols = lake
    eng, clock = _engine(sessions[128], None, window=8, flush_after=1.0)
    r1 = eng.submit(query, q_cols)
    assert eng.pump() == []
    clock["t"] = 0.99
    assert eng.pump() == []  # deadline not yet reached
    clock["t"] = 1.0
    served = eng.pump()  # oldest request aged past flush_after
    assert served == [r1] and r1.done
    # future carries the payload
    entries, stats = r1.future.result(timeout=0)
    assert entries == r1.results and stats is r1.stats
    ref, _ = discovery.discover(eng.index, query, q_cols, k=5)
    assert _same_set(r1.results, ref)


def test_engine_no_deadline_only_full_windows(sessions, lake):
    _corpus, query, q_cols = lake
    eng, clock = _engine(sessions[128], None, window=4, flush_after=None)
    eng.submit(query, q_cols)
    clock["t"] = 1e9
    assert eng.pump() == []  # no deadline policy: partial group waits
    assert eng.flush()  # explicit flush always drains
    assert not eng.queue


def test_engine_deadline_serves_multiple_due_groups(sessions, lake):
    corpus, query, q_cols = lake
    qs = [(query, q_cols)] * 5
    eng, clock = _engine(sessions[128], None, window=2, flush_after=0.5)
    for q, qc in qs:
        eng.submit(q, qc)
    clock["t"] = 1.0
    served = eng.pump()  # two full windows + one deadline-due partial
    assert len(served) == 5 and all(r.done for r in served)


def test_engine_per_request_k(sessions, lake):
    _corpus, query, q_cols = lake
    eng, _clock = _engine(sessions[128], None, window=2, flush_after=None)
    r_a = eng.submit(query, q_cols, k=3)
    r_b = eng.submit(query, q_cols)  # config default k=5
    eng.pump()
    assert len(r_a.results) <= 3
    ref3, _ = discovery.discover(eng.index, query, q_cols, k=3)
    ref5, _ = discovery.discover(eng.index, query, q_cols, k=5)
    assert _same_set(r_a.results, ref3)
    assert _same_set(r_b.results, ref5)


def test_engine_next_deadline(sessions, lake):
    _corpus, query, q_cols = lake
    eng, clock = _engine(sessions[128], None, window=4, flush_after=2.0)
    assert eng.next_deadline() is None
    clock["t"] = 1.0
    eng.submit(query, q_cols)
    assert eng.next_deadline() == pytest.approx(3.0)


def test_engine_discover_async(sessions, lake):
    corpus, query, q_cols = lake
    queries = [(query, q_cols)] + synthetic.make_mixed_queries(
        corpus, 2, 10, 2, seed=33
    )
    session = MateSession(
        sessions[128].index, DiscoveryConfig(window=4, flush_after=0.02, k=5)
    )
    eng = DiscoveryEngine(session=session)

    async def run():
        return await asyncio.gather(
            *[eng.discover_async(q, qc) for q, qc in queries]
        )

    reqs = asyncio.run(run())
    assert all(r.done for r in reqs)
    for (q, qc), r in zip(queries, reqs):
        ref, _ = discovery.discover(eng.index, q, qc, k=5)
        assert sorted((e.table_id, e.joinability) for e in r.results) == sorted(
            (e.table_id, e.joinability) for e in ref
        )


def test_engine_discover_async_without_deadline_policy(sessions, lake):
    """Regression: with flush_after=None an async waiter must drain its
    group rather than spin forever waiting for a window that never fills."""
    _corpus, query, q_cols = lake
    eng = DiscoveryEngine(
        session=MateSession(sessions[128].index, DiscoveryConfig(k=5))
    )  # default config: window=8, no deadline

    async def run():
        return await asyncio.wait_for(
            eng.discover_async(query, q_cols), timeout=30.0
        )

    req = asyncio.run(run())
    assert req.done
    ref, _ = discovery.discover(eng.index, query, q_cols, k=5)
    assert _same_set(req.results, ref)


def test_engine_group_failure_rejects_every_future(sessions, lake):
    """Regression: when a group launch raises, every dequeued request's
    future must be rejected — a sibling awaiter must not hang forever."""
    _corpus, query, q_cols = lake
    eng, _clock = _engine(sessions[128], None, window=2, flush_after=None)
    good = eng.submit(query, q_cols)
    bad = eng.submit(query, [99])  # column index out of range -> IndexError
    with pytest.raises(IndexError):
        eng.pump()
    assert good.future.done() and bad.future.done()
    with pytest.raises(IndexError):
        good.future.result(timeout=0)
    assert not eng.queue  # the failed group is not silently requeued

    # flush(): a failing FIRST group must leave later groups queued with
    # pending futures, not strand them dequeued-and-unresolved
    bad2 = eng.submit(query, [99])
    pad = eng.submit(query, [99])
    later = eng.submit(query, q_cols)
    with pytest.raises(IndexError):
        eng.flush()
    assert bad2.future.done() and pad.future.done()
    assert not later.future.done() and eng.queue == [later]
    eng.flush()  # retry serves the still-queued survivor
    assert later.done and later.future.result(timeout=0)

    async def run():
        return await asyncio.wait_for(
            eng.discover_async(query, [99]), timeout=30.0
        )

    with pytest.raises(IndexError):
        asyncio.run(run())


def test_engine_session_and_index_conflict(sessions):
    with pytest.raises(TypeError):
        DiscoveryEngine(sessions[128].index, session=sessions[128])
    with pytest.raises(TypeError):
        DiscoveryEngine()


def test_engine_removed_legacy_flags_cannot_touch_session(sessions, lake):
    """The removed use_kernel=/fused= flags raise before they could ever
    touch a shared session's once-resolved backend."""
    session = MateSession(sessions[128].index, DiscoveryConfig(backend="xla"))
    with pytest.raises(TypeError, match="fused"):
        DiscoveryEngine(session=session, fused=True)
    assert session.backend.name == "xla"  # untouched


def test_enrich_accepts_session(sessions, lake):
    from repro.data.enrichment import enrich
    from repro.core.corpus import Table

    corpus, query, q_cols = lake
    session = sessions[128]
    base = Table(-1, [list(r) for r in corpus.tables[0].cells[:8]])
    served_before = session.stats.requests
    enriched_s, prov_s = enrich(session, base, key_cols=[0], k=3)
    enriched_i, prov_i = enrich(session.index, base, key_cols=[0], k=3)
    assert [r for r in enriched_s.cells] == [r for r in enriched_i.cells]
    assert prov_s == prov_i
    assert session.stats.requests == served_before + 1
