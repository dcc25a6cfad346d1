"""One benchmark run of one cell on the chip it finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order: turn on JAX's persistent compile cache; fail unless JAX finds
the cell's TPU chips; generate the cell's lake (from the configuration's
seed) and the mix's requests and schedule (from the mix's seed); lay the
lake out in a table and row order drawn from the run's seed, and order the
rows of each request by it too; index the lake with ``MateSession.build``
on the platform's default backend; warm up every program those requests
run; offer them open-loop through ``AsyncDiscoveryEngine`` for
``--seconds``; check every answer against the plain reference; print one
JSON line.  With ``--trace 0`` the line holds the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from host spans and
a profiler trace of part of the window.

The numbers that decide ``correct`` are printed, each beside its limit, as
the last lines of standard error and under ``checks``, the result's last
key.  Without the cell's chips the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference, traffic, trace as trace_lib, window  # noqa: E402
from bench.catalog import Catalog  # noqa: E402
from bench.lake import rng as seeded  # noqa: E402
from bench.spans import Spans, instrument  # noqa: E402

# seconds a request may still take once the window has closed
GRACE = 60.0
# the profiler records this share of the window, from TRACE_AT on
TRACE_AT, TRACE_SHARE = 0.3, 0.4
# the event JAX records each time it lowers a program: a compile, or a
# fetch from the persistent cache
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator of the cell's kind, or too few of them."""


@dataclasses.dataclass
class Run:
    """What one run saw; the per-layer metric modules read it."""

    cell: dict
    config: dict
    seconds: float
    t0: float = 0.0
    t_end: float = 0.0
    outcomes: list = dataclasses.field(default_factory=list)
    spans: Spans | None = None
    trace: dict | None = None
    trace_bounds: tuple[float, float] | None = None
    stats: object = None
    build_stats: object = None
    compiles: list = dataclasses.field(default_factory=list)
    peaks: dict | None = None
    work: object = None

    def compiles_between(self, lo: float, hi: float) -> int:
        return sum(1 for start in self.compiles if lo <= start <= hi)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def devices_or_fail(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no tpu device: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def check_backend(backend) -> None:
    """The served path has to be the chip's own: the gather-fused kernel,
    chosen by the platform."""
    if (backend.name, backend.source) != ("fused-gather", "platform"):
        raise RuntimeError(f"backend {backend.name}[{backend.source}], want fused-gather[platform]")


def warm_up(session, queries, window_size: int) -> None:
    """Compile every program the window can run.  The kernels' shapes
    follow each serving group's exact sizes, and the engine only ever
    groups consecutive requests, so every request runs once alone (its
    hashing, gate, launch and scoring shapes) and every run of 2..window
    consecutive requests runs its shared launch.  Plans are computed once
    per request here and dropped afterwards; the window plans afresh, and
    the result and bound caches stay off."""
    from repro.core import batched

    plans: dict[int, object] = {}
    plan_query = batched.plan_query

    def planned(index, query, q_cols, *args, **kwargs):
        if id(query) not in plans:
            plans[id(query)] = plan_query(index, query, q_cols, *args, **kwargs)
        return plans[id(query)]

    batched.plan_query = planned
    try:
        for q in queries:
            (pc,) = session.plan_and_count([q])
            # k=1 prunes nearly every table before verification; the
            # scoring programs' shapes do not depend on k
            session.score_from_counts(pc, k=1)
        for size in range(2, window_size + 1):
            for i in range(len(queries) - size + 1):
                session.plan_and_count(queries[i : i + size])
    finally:
        batched.plan_query = plan_query


def serve(session, queries, dues, seconds: float, marks=(), grace: float = GRACE):
    from repro.serve.engine import AsyncDiscoveryEngine

    async def go():
        engine = AsyncDiscoveryEngine(session=session)
        await engine.start()
        try:
            return await window.drive(engine, dues, queries, seconds, grace=grace, marks=marks)
        finally:
            await engine.stop(drain=False)

    return asyncio.run(go())


def check_answers(lake, requests, outcomes, k: int) -> tuple[int, int, list[str]]:
    """(wrong answers, missing answers, the first few reasons)."""
    ref = reference.Reference(lake)
    wrong, missing, why = 0, 0, []
    for i, (req, out) in enumerate(zip(requests, outcomes)):
        if out.error is not None:
            missing += 1
            why.append(f"request {i}: {out.error}")
            continue
        bad = reference.check(out.entries, ref.joinability(req.key[:, : req.key_width]), k)
        if bad:
            wrong += 1
            why.append(f"request {i} ({req.n_rows} rows, width {req.key_width}): {bad}")
    return wrong, missing, why[:5]


@dataclasses.dataclass
class Prepared:
    """A cell made ready to serve: its lake, requests and warm session."""

    cell: dict
    config: dict
    lake: object
    requests: list
    queries: list
    session: object
    build_s: float
    devices: list
    compiles: list


def prepare(cat: Catalog, workload: str, seed: int, seconds: float) -> Prepared:
    """Everything before the window: chips, lake, index, requests, warm-up."""
    cell = cat.cell(workload)
    config = cat.config(cell["config"])
    mix = cat.traffic(cell["traffic"])
    src = cat.root / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"the system under test is missing: no {src / 'repro'}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))

    import jax.monitoring

    from repro.core.corpus import Table
    from repro.core.session import DiscoveryConfig, MateSession, SessionStats
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = devices_or_fail(cell["chips"])
    compiles: list[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(time.perf_counter() - secs)
        if event == LOWERING_EVENT
        else None
    )

    t = time.perf_counter()
    lake = cat.module("lakes", config["lake"]["generator"]).generate(
        config["lake"]["params"], config["lake"]["seed"]
    )
    requests = traffic.generate(
        mix, cat.module("traffic", mix["generator"]).query, lake, seed, seconds
    )
    # the requests hold vocabulary ids, so the layout leaves them as drawn
    lake = lake.shuffled(seeded(seed, 5))
    log(
        f"lake: {len(lake.tables)} tables, {lake.total_rows} rows, {lake.total_cells} cells, "
        f"{len(lake.vocab)} values; {len(requests)} requests; generated in "
        f"{time.perf_counter() - t:.3f} s"
    )

    serving = config["serving"]
    t = time.perf_counter()
    session = MateSession.build(
        lake.to_corpus(),
        DiscoveryConfig(
            bits=config["bits"],
            k=serving["k"],
            window=serving["window"],
            flush_after=serving["flush_after"],
            rank=serving["rank"],
            profile_gate=serving["profile_gate"],
            result_cache=serving["result_cache"],
            bound_cache=serving["bound_cache"],
        ),
    )
    build_s = time.perf_counter() - t
    backend = session.backend
    check_backend(backend)
    log(f"build: {build_s:.3f} s, backend {backend.name}[{backend.source}]")

    queries = [(Table(-1, lake.strings(r.key)), list(range(r.key_width))) for r in requests]
    t = time.perf_counter()
    n_before = len(compiles)
    warm_up(session, queries, serving["window"])
    log(f"warm-up: {time.perf_counter() - t:.3f} s, {len(compiles) - n_before} programs lowered")
    session.stats = SessionStats()
    return Prepared(cell, config, lake, requests, queries, session, build_s, devices, compiles)


def run(
    workload: str,
    seed: int,
    seconds: float,
    traced: bool,
    *,
    root: Path = ROOT,
) -> dict:
    """One run; returns the result line's object."""
    cat = Catalog(root)
    prep = prepare(cat, workload, seed, seconds)
    cell, config, session, devices = prep.cell, prep.config, prep.session, prep.devices
    lake, requests, queries, compiles, build_s = (
        prep.lake, prep.requests, prep.queries, prep.compiles, prep.build_s
    )
    del prep

    info = Run(cell=cell, config=config, seconds=seconds)
    marks = []
    trace_dir = cat.root / ".bench_run" / "trace" / workload
    if traced:
        info.spans = Spans()
        instrument(info.spans)
        shutil.rmtree(trace_dir, ignore_errors=True)
        bounds: list[float] = []
        marks = [
            (seconds * TRACE_AT, lambda: bounds.append(trace_lib.start(trace_dir))),
            (seconds * (TRACE_AT + TRACE_SHARE), lambda: bounds.append(trace_lib.stop())),
        ]
    setup_s = time.perf_counter() - T0
    log(f"set-up: {setup_s:.3f} s")

    outcomes, t0 = serve(session, queries, [r.due for r in requests], seconds, marks)
    if info.spans is not None:
        info.spans.restore()
    info.t0, info.outcomes, info.compiles = t0, outcomes, compiles
    info.t_end = max([o.done for o in outcomes if o.done == o.done] + [t0 + seconds])
    info.stats, info.build_stats = session.stats, session.build_stats
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    dev = devices[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(memory_peak),
    }
    del session, queries
    gc.collect()

    lat = window.latencies(outcomes)
    late = window.lateness(outcomes)
    in_window = info.compiles_between(t0, info.t_end)
    log(
        f"window: {len(outcomes)} requests due, {lat.size} answered; generator late "
        f"mean {late.mean():.4f} s, max {late.max():.4f} s; "
        f"programs lowered in the window: {in_window}"
    )
    result: dict = {"correct": False, "attempted": len(outcomes)}
    result["failed"] = sum(1 for o in outcomes if o.error is not None)

    if traced:
        info.peaks = trace_lib.peaks(dev.device_kind, cat.root)
        info.trace_bounds = (bounds[0], bounds[1]) if len(bounds) == 2 else None
        info.trace = trace_lib.reduce(trace_lib.load(trace_dir), n_devices=cell["chips"])
        info.work = cat.module("work", "gather_filter")
        metrics = {}
        for m in cat.per_layer(workload):
            value = cat.module("metrics", m["name"]).read(info)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = info.trace["busy_s"]
        device["window_s"] = info.trace["window_s"]
        result["breakdown"] = info.trace["breakdown"]
    else:
        answered_by_close = window.completed_rate(outcomes, t0, seconds)
        e2e = {
            "discover_p50_s": window.percentile(lat, 50) if lat.size else None,
            "discover_p95_s": window.percentile(lat, 95) if lat.size else None,
            "discover_rps": answered_by_close,
            "build_s": build_s,
            "setup_s": setup_s,
        }
        log(f"latency over {lat.size} requests; {answered_by_close * seconds:.0f} answered by the close")
        metrics = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cat.end_to_end(workload)
            if e2e.get(m["name"]) is not None
        }
    result["metrics"] = metrics
    result["device"] = device

    t = time.perf_counter()
    wrong, missing, why = check_answers(lake, requests, outcomes, config["serving"]["k"])
    for line in why:
        log(f"check: {line}")
    log(f"reference: {len(outcomes)} requests checked in {time.perf_counter() - t:.3f} s")
    checks = {
        "wrong_answers": {"value": wrong, "limit": 0},
        "missing_answers": {"value": missing, "limit": 0},
    }
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    for name, c in checks.items():
        log(f"{name}: {c['value']} (limit {c['limit']})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except (NoChip, FileNotFoundError, KeyError) as e:
        log(f"FAIL: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
