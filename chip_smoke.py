"""Run MATE's served discovery path once on a TPU and check every answer.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the routed lake over a 4-chip mesh

One chip: a synthetic lake made from ``--seed`` (32768 tables, about 1.05M
corpus rows; a serving group spans more tables than the fused kernel's
8192-table cap, so launches split) is indexed at 512 bits by
``MateSession.build`` on the platform-default backend.  Mixed
join-discovery traffic (query sizes 100-1000 rows, key widths 2-4) goes
through ``AsyncDiscoveryEngine``, all of it cold and a part of it warm, then
one FD request, then one §5.4 ``update_cell`` and one more query.  Every
answer is compared with a numpy-backend session over the same index: CPU-only
worker processes rebuild the lake from the seed, check that their index is
byte-identical, and answer the traffic meanwhile.

Four chips: the same kind of lake (4096 tables by default) and traffic
through a routed session (``MateSession.build(..., distributed=True,
n_shards=4)`` attached to a 4-device mesh), compared with a single-device
session on the same corpus, plus a check that each shard's store sits on its
own chip.

Earlier lines say what ran; their wall times are not a benchmark.  The last
line is one JSON object, ``{"ok": true, "device": {...}}``.  Without a TPU
the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import multiprocessing
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# the platform the run must find; a CPU rehearsal of this script overrides
# it, and then expects the Pallas kernels in interpret mode instead
PLATFORM = "tpu"
DWTC_ROWS = 1_450_000_000  # the paper's web-table lake, which W1 models
BITS = 512
# requests per shared launch: two, so the smoke runs a group launch
WINDOW = 2
# the warm pass repeats this many of the requests, with every kernel compiled
WARM = 8
# CPU-only processes computing the numpy reference
REFERENCE_WORKERS = 4


def _fail(msg: str) -> None:
    print(f"[smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _check(ok: bool, msg: str) -> None:
    if not ok:
        _fail(msg)


def _say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class _PallasSpy:
    """Records the ``interpret`` flag of every Pallas kernel traced."""

    def __init__(self):
        from jax.experimental import pallas as pl

        self.flags: list[bool] = []
        self._pl, self._orig = pl, pl.pallas_call
        pl.pallas_call = self._call

    def _call(self, *args, **kwargs):
        self.flags.append(bool(kwargs.get("interpret", False)))
        return self._orig(*args, **kwargs)

    def check(self) -> None:
        want = PLATFORM != "tpu"
        _check(bool(self.flags), "no Pallas kernel was traced")
        _check(
            all(f == want for f in self.flags),
            f"Pallas kernels traced with interpret={not want}",
        )


class _CacheCounter:
    """Counts persistent compilation-cache hits and misses."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)

    def _event(self, event: str, **_kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _lake(n_tables: int, seed: int):
    from repro.data import synthetic

    return synthetic.make_corpus(
        synthetic.SyntheticSpec(n_tables=n_tables, seed=seed)
    )


def _make_lake(n_tables: int, seed: int):
    t0 = time.perf_counter()
    corpus = _lake(n_tables, seed)
    dt = time.perf_counter() - t0
    _say(
        f"lake: {n_tables} tables, {corpus.total_rows} rows, "
        f"{len(corpus.unique_values)} unique values, generated in {dt:.1f} s"
    )
    _say(
        f"cut against W1: {corpus.total_rows} of DWTC's {DWTC_ROWS} rows "
        f"({corpus.total_rows / DWTC_ROWS:.2e})"
    )
    return corpus


def _traffic(corpus, n_requests: int, seed: int):
    """``make_mixed_queries`` requests with 100-1000 rows, key widths 2-4."""
    from repro.data import synthetic

    rng = np.random.default_rng(seed)
    out = []
    while len(out) < n_requests:
        out += synthetic.make_mixed_queries(
            corpus, 1, int(rng.integers(100, 1001)),
            key_width=int(rng.integers(2, 5)), seed=int(rng.integers(1 << 30)),
        )
    return out


async def _serve_async(session, queries):
    from repro.serve.engine import AsyncDiscoveryEngine

    async with AsyncDiscoveryEngine(session=session) as engine:

        async def one(query, q_cols):
            t0 = time.perf_counter()
            req = await engine.discover_async(query, q_cols)
            return req.results, time.perf_counter() - t0

        out = await asyncio.gather(*(one(q, c) for q, c in queries))
    return [r for r, _ in out], [t for _, t in out]


def _serve(session, queries):
    """Answers and per-request wall seconds through ``AsyncDiscoveryEngine``."""
    return asyncio.run(_serve_async(session, queries))


def _topk(entries) -> list[tuple[int, int]]:
    return sorted((e.table_id, e.joinability) for e in entries)


def _verdicts(candidates) -> list[tuple]:
    return sorted((c.table_id, c.support, c.holds, c.violations) for c in candidates)


def _check_answers(got, want, what: str) -> None:
    """``got``: served top-k entry lists; ``want``: reference ``_topk`` sets."""
    bad = [i for i, (g, w) in enumerate(zip(got, want)) if _topk(g) != w]
    _check(len(got) == len(want) and not bad, f"{what}: top-k differs at {bad}")


def _index_digest(index) -> str:
    return hashlib.blake2b(index.superkeys.tobytes(), digest_size=16).hexdigest()


def _cpu_only() -> None:
    # runs in each reference worker before it imports JAX: the chip belongs
    # to the parent process
    os.environ["JAX_PLATFORMS"] = "cpu"


def _reference_part(n_tables: int, seed: int, n_requests: int, part: int, parts: int):
    """One reference worker's share: rebuild the lake and its index from the
    seed, answer every ``parts``-th request (and, in part 0, the FD probe)
    with a numpy-backend session.  Returns the index digest, ``{request:
    top-k set}`` and the FD verdicts (None outside part 0)."""
    from repro.core.session import DiscoveryConfig, MateSession

    corpus = _lake(n_tables, seed)
    queries = _traffic(corpus, n_requests, seed + 1)
    session = MateSession.build(corpus, DiscoveryConfig(bits=BITS, backend="numpy"))
    answers = {
        i: _topk(session.discover(q, c)[0])
        for i, (q, c) in enumerate(queries)
        if i % parts == part
    }
    fds = None
    if part == 0:
        fds = _verdicts(session.discover_fds(*_fd_probe(*queries[0]))[0])
    return _index_digest(session.index), answers, fds


def _start_reference(pool, args, parts: int):
    return [
        pool.apply_async(
            _reference_part, (args.n_tables, args.seed, args.requests, p, parts)
        )
        for p in range(parts)
    ]


def _collect_reference(jobs, index, n_requests: int):
    """Wait for the reference workers; their indexes must equal ``index``."""
    t0 = time.perf_counter()
    want: dict[int, list] = {}
    fds = None
    digest = _index_digest(index)
    for job in jobs:
        d, answers, part_fds = job.get()
        _check(d == digest, "a reference worker built a different index")
        want.update(answers)
        fds = part_fds if part_fds is not None else fds
    _say(
        f"numpy reference: {len(jobs)} CPU workers, same index digest "
        f"{digest}, waited {time.perf_counter() - t0:.1f} s"
    )
    return [want[i] for i in range(n_requests)], fds


def _walls(label: str, walls: list[float]) -> None:
    _say(
        f"{label} per-request wall (not a benchmark): "
        f"median {np.median(walls):.3f} s, max {max(walls):.3f} s "
        f"over {len(walls)} requests"
    )


def _memory(dev) -> None:
    stats = dev.memory_stats() or {}
    _say(
        f"device memory: bytes_in_use={stats.get('bytes_in_use')} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}"
    )


def _fd_probe(query, q_cols):
    """The first query plus an FD-clean dependent column and one
    conflicting duplicate key, so a violating group exists."""
    from repro.core.corpus import Table

    dep = query.n_cols
    cells = [list(r) + [f"dep{i}"] for i, r in enumerate(query.cells)]
    cells.append(list(query.cells[0]) + ["dep-conflict"])
    return Table(-1, cells, name="fd probe"), list(q_cols), dep


def one_chip(args) -> None:
    # the reference workers rebuild the lake themselves, alongside this
    # process's own build and serving
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(REFERENCE_WORKERS, initializer=_cpu_only) as pool:
        jobs = _start_reference(pool, args, REFERENCE_WORKERS)
        _serve_and_check(args, jobs)


def _serve_and_check(args, jobs) -> None:
    from repro.core.session import DiscoveryConfig, MateSession

    corpus = _make_lake(args.n_tables, args.seed)
    queries = _traffic(corpus, args.requests, args.seed + 1)
    config = DiscoveryConfig(bits=BITS, window=WINDOW, flush_after=0.005)
    t0 = time.perf_counter()
    session = MateSession.build(corpus, config)
    index = session.index
    _say(
        f"index: built in {time.perf_counter() - t0:.1f} s, "
        f"{index.superkeys.nbytes} superkey bytes at {BITS} bits"
    )
    backend = session.backend
    _check(
        (backend.name, backend.source) == ("fused-gather", "platform")
        or PLATFORM != "tpu",
        f"backend {backend.name}[{backend.source}], want fused-gather[platform]",
    )
    _say(f"backend: {backend.name}[{backend.source}]")

    t0 = time.perf_counter()
    first, _ = _serve(session, queries[:1])
    _say(f"first query: {time.perf_counter() - t0:.1f} s, compiles included")
    cold, walls = _serve(session, queries)
    _walls("cold", walls)
    warm, walls = _serve(session, queries[:WARM])
    _walls("warm", walls)
    fd_query, det, dep = _fd_probe(*queries[0])
    fds, _ = session.discover_fds(fd_query, det, dep)

    want, fds_ref = _collect_reference(jobs, index, len(queries))
    _check_answers(first, want[:1], "first query")
    _check_answers(cold, want, "cold traffic")
    _check_answers(warm, want[:WARM], "warm traffic")
    _say(
        f"{len(first) + len(cold) + len(warm)} requests: top-k sets match "
        "the numpy session"
    )
    _check(_verdicts(fds) == fds_ref, "FD verdicts differ")
    _say(f"FD request: {len(fds)} verdicts match the numpy session")

    # §5.4 update inside a table a query joins, on a row holding one of its
    # key values, so the answer itself can move
    i = next((i for i, w in enumerate(want) if w), 0)
    _check(bool(want[i]), "no query found a joinable table")
    q0, c0 = queries[i]
    top = cold[i][0]
    col = top.mapping[0]
    keys0 = {row[c0[0]] for row in q0.cells}
    cells = corpus.tables[top.table_id].cells
    row = next((r for r, cs in enumerate(cells) if cs[col] in keys0), 0)
    store = index.device_store()
    session.update_cell(top.table_id, row, col, "smoke-updated-value")
    after, _ = _serve(session, [queries[i]])
    reference = MateSession(index, DiscoveryConfig(bits=BITS, backend="numpy"))
    _check_answers(after, [_topk(reference.discover(q0, c0)[0])], "after update_cell")
    fresh = index.device_store()
    lines = np.asarray(fresh.lines).reshape(-1)
    n, lanes = index.superkeys.shape
    _check(
        fresh is not store
        and np.array_equal(lines[: n * lanes].reshape(n, lanes), index.superkeys),
        "device store did not refresh after update_cell",
    )
    _say(f"update_cell(table {top.table_id}, row {row}, col {col}): store refreshed, answer matches")

    st = session.stats
    _say(
        f"launches: requests={st.requests} "
        f"filter_fused_launches={st.filter_fused_launches} "
        f"gather_bytes_saved={st.gather_bytes_saved} "
        f"gather_demotions={st.gather_demotions} "
        f"shard_gather_demotions={st.shard_gather_demotions}"
    )
    _check(st.filter_fused_launches > 0, "no fused launch ran")
    _check(
        st.gather_demotions == 0 and st.shard_gather_demotions == 0,
        "a launch was demoted off the gather-fused path",
    )


def four_chips(args) -> None:
    import jax
    from jax.sharding import Mesh

    from repro.core import distributed
    from repro.core.session import DiscoveryConfig, MateSession

    devices = jax.devices()[:4]
    _check(len(devices) == 4, f"--chips 4 needs 4 devices, found {len(devices)}")
    corpus = _make_lake(args.n_tables, args.seed)
    queries = _traffic(corpus, args.requests, args.seed + 1)
    config = DiscoveryConfig(bits=BITS, window=WINDOW, flush_after=0.005)
    t0 = time.perf_counter()
    single = MateSession.build(corpus, config)
    routed = MateSession.build(corpus, config, distributed=True, n_shards=4)
    routed.index.attach_mesh(Mesh(np.asarray(devices), ("shard",)))
    _say(f"single-device and routed sessions built in {time.perf_counter() - t0:.1f} s")

    want, _ = _serve(single, queries)
    got, walls = _serve(routed, queries)
    _check_answers(got, [_topk(w) for w in want], "routed traffic")
    _walls("routed, cold", walls)
    _say(f"{len(queries)} requests: routed top-k sets match the single-device session")

    homes = [set(s.device_store().lines.devices()) for s in routed.index.shards]
    _check(
        all(len(h) == 1 for h in homes) and len(set().union(*homes)) == 4,
        f"shard stores are not one per chip: {homes}",
    )
    mesh_store, _ = distributed._routed_mesh_store(routed.index)
    _check(
        len(mesh_store.sharding.device_set) == 4,
        "the mesh store does not span 4 devices",
    )
    _say(
        "placement: shard stores on "
        f"{sorted(d.id for h in homes for d in h)}, mesh store over "
        f"{len(mesh_store.sharding.device_set)} devices"
    )
    st = routed.stats
    _say(
        f"launches: shard_launches={st.shard_launches} "
        f"filter_fused_launches={st.filter_fused_launches} "
        f"route_bytes_merged={st.route_bytes_merged} "
        f"shard_gather_demotions={st.shard_gather_demotions}"
    )
    _check(st.filter_fused_launches > 0, "no fused shard launch ran")
    _check(st.shard_gather_demotions == 0, "a shard launch was demoted")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--n-tables", type=int, default=None,
        help="lake size (default: 32768 on one chip, 4096 on four)",
    )
    ap.add_argument("--requests", type=int, default=32)
    args = ap.parse_args(argv)
    if args.n_tables is None:
        args.n_tables = 4096 if args.chips == 4 else 32768

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache = _CacheCounter()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != PLATFORM:
        _fail(f"no {PLATFORM} device: JAX found {dev.platform}")
    spy = _PallasSpy()
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args)
    spy.check()
    _say(
        f"Pallas kernels traced: {len(spy.flags)}, "
        f"interpret={PLATFORM != 'tpu'} for all"
    )
    _memory(dev)
    _say(
        f"compile cache {cache_dir}: hits={cache.hits} misses={cache.misses}"
    )
    _say(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }))


if __name__ == "__main__":
    main()
