"""BENCHMARK.json and the files it names keep the format the harness reads,
and a cell's parts are found by name."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.catalog import Catalog
from bench.tests import cpu_run

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {"discover_p50_s", "discover_rps", "build_s", "setup_s"}


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"] and SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    # 24 cells of 14 runs each, with their compiles, fit in 12 hours
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs():
    used = {w["config"] for w in SPEC["workloads"]}
    files = set()
    assert SPEC["configs"]
    for cfg in SPEC["configs"]:
        assert set(cfg) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(cfg["name"]) and cfg["name"] in used
        assert _line(cfg["source"]) and _line(cfg["why"]) and cfg["file"].startswith("bench/")
        assert cfg["file"] not in files
        files.add(cfg["file"])
        body = json.loads((ROOT / cfg["file"]).read_text())
        assert body["name"] == cfg["name"]
        assert sorted(cfg["reduced"]) == sorted(body["reduced"])
        assert set(cfg["reduced"]) <= set(body["lake"]["params"])
        for key in ("source", "serving", "guarantees", "assumed", "bits"):
            assert key in body
        assert set(body["serving"]) >= {"window", "flush_after", "k", "rank", "profile_gate"}


def test_workloads():
    cat = Catalog(ROOT)
    names = [w["name"] for w in SPEC["workloads"]]
    assert len(set(names)) == len(names)
    assert len({(w["config"], w["traffic"]) for w in SPEC["workloads"]}) == len(names)
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert _line(w["why"])
        mix = cat.traffic(w["traffic"])
        assert mix["rate"] > 0
        cat.module("traffic", mix["generator"])
        # every cell reports every end-to-end metric and some per-layer one
        assert {m["name"] for m in cat.end_to_end(w["name"])} == E2E
        assert cat.per_layer(w["name"])


def test_metrics():
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(names)) == len(names)
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace") and UNIT.match(m["unit"])
        assert 0.01 <= m["bound"] <= 0.25 and m["better"] in ("lower", "higher")
    layers: dict[str, str] = {}
    cat = Catalog(ROOT)
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and _line(m["layer"])
        assert m["moves"] in E2E - {"setup_s"}
        mod = cat.module("metrics", m["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (m["layer"], m["unit"], m["source"], m["moves"])
        layers.setdefault(m["layer"], m["layer"])
    assert {"filter kernel", "planning", "serving tier", "device"} <= set(layers)


def _cpu_env() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    return env


def test_exits_without_a_chip():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SPEC["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_cpu_env(), capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0 and out.stdout == ""
    assert "no tpu device" in out.stderr


def test_exits_in_a_tree_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SPEC["workloads"][0]["name"], "--seed", "2",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_cpu_env(), capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0 and out.stdout == ""


def test_new_parts_are_found_by_name(tmp_path):
    """A configuration, a traffic mix, a metric and a cell added as new files
    and entries, with no existing file edited."""
    tree = cpu_run.tiny_tree(tmp_path)
    cfg = json.loads((tree / "bench/configs/webtable-dwtc.json").read_text())
    cfg["name"] = "webtable-narrow"
    cfg["lake"]["params"].update(cols_hi=4)
    (tree / "bench/configs/webtable-narrow.json").write_text(json.dumps(cfg))
    mix = json.loads((tree / "bench/traffic/fp-nary.json").read_text())
    mix.update(rows=[10], key_width=[2])
    (tree / "bench/traffic/short.json").write_text(json.dumps(mix))
    (tree / "bench/metrics/answered.py").write_text(
        'LAYER = "serving tier"\nUNIT = "count"\nSOURCE = "program_counter"\n'
        'MOVES = "discover_rps"\n\n\ndef read(run):\n    return len(run.outcomes)\n'
    )
    spec = json.loads((tree / "BENCHMARK.json").read_text())
    spec["configs"].append({**spec["configs"][0], "name": "webtable-narrow",
                            "file": "bench/configs/webtable-narrow.json"})
    spec["workloads"].append({"name": "narrow-short", "config": "webtable-narrow",
                              "traffic": "short", "chips": 1, "why": "a test cell"})
    spec["per_layer"].append({"name": "answered", "unit": "count", "better": "higher",
                              "source": "program_counter", "layer": "serving tier",
                              "moves": "discover_rps", "workloads": ["narrow-short"]})
    (tree / "BENCHMARK.json").write_text(json.dumps(spec))

    cat = Catalog(tree)
    assert cat.config("webtable-narrow")["lake"]["params"]["cols_hi"] == 4
    assert cat.traffic("short")["rows"] == [10]
    assert [m["name"] for m in cat.per_layer("narrow-short")] == ["answered"]
    assert cat.module("metrics", "answered").read(type("R", (), {"outcomes": [1, 2]})) == 2

    result = _tiny_run(tree, "narrow-short")
    assert result["correct"] is True and result["attempted"] == 12
    assert set(result["metrics"]) == E2E


NEW_CONFIG = Path(__file__).resolve().parent / "data" / "new_config"


def _full_tree(dst: Path) -> Path:
    """A copy of the benchmark's files, at full size."""
    shutil.copytree(ROOT / "bench", dst / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    return dst


def _add(tree: Path, files: Path, entries: dict) -> None:
    """Add new files under ``tree`` and append new entries to its
    ``BENCHMARK.json``; assert that no file already there changed."""
    bench_json = tree / "BENCHMARK.json"
    before = {p: p.read_bytes() for p in tree.rglob("*") if p.is_file()}
    for path in files.rglob("*"):
        if path.is_file():
            dst = tree / path.relative_to(files)
            assert not dst.exists(), dst
            dst.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(path, dst)
    spec = json.loads(bench_json.read_text())
    for key, new in entries.items():
        spec[key] = spec[key] + new
    bench_json.write_text(json.dumps(spec))
    assert [p for p, b in before.items() if p != bench_json and p.read_bytes() != b] == []
    old = json.loads(before[bench_json])
    assert {k: v[: len(old[k])] if k in entries else v for k, v in spec.items()} == old


def test_a_new_lake_generator_needs_no_edit(tmp_path):
    """A configuration whose lake generator is a new module (a two-relation
    lake with a composite foreign key), with a new traffic generator, mix,
    cell and metric, added as new files and new entries of BENCHMARK.json:
    no file already in the tree changes, each generator's own TINY cuts it
    for the CPU, and the new cell's run is correct."""
    full = _full_tree(tmp_path / "full")
    _add(full, NEW_CONFIG / "files", json.loads((NEW_CONFIG / "entries.json").read_text()))
    tree = cpu_run.tiny_tree(tmp_path / "tiny", full)

    cat = Catalog(tree)
    assert cat.config("supply-lake")["lake"]["params"]["parts"] == 40
    assert cat.traffic("lineitem-partsupp")["rows"] == [10, 40]
    assert [m["name"] for m in cat.per_layer("supply-fk")] == ["requests_answered"]
    assert cat.module("metrics", "requests_answered").read(type("R", (), {"outcomes": [1]})) == 1
    result = _tiny_run(tree, "supply-fk")
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 12
    assert set(result["metrics"]) == E2E


def test_a_generator_without_tiny_is_named(tmp_path):
    full = _full_tree(tmp_path / "full")
    cfg = json.loads((full / SPEC["configs"][0]["file"]).read_text())
    cfg["lake"]["generator"] = "bare"
    files = tmp_path / "files"
    (files / "bench/configs").mkdir(parents=True)
    (files / "bench/lakes").mkdir()
    (files / "bench/configs/bare.json").write_text(json.dumps(cfg))
    (files / "bench/lakes/bare.py").write_text("from bench.lakes.webtable import generate  # noqa: F401\n")
    _add(full, files, {
        "configs": [{**SPEC["configs"][0], "name": "bare", "file": "bench/configs/bare.json"}],
        "workloads": [{**SPEC["workloads"][0], "name": "bare-cell", "config": "bare"}],
    })
    with pytest.raises(AttributeError, match=r"bench/lakes/bare\.py declares no TINY"):
        cpu_run.tiny_tree(tmp_path / "tiny", full)


def _tiny_run(tree: Path, cell: str, fault: str | None = None) -> dict:
    args = [sys.executable, str(ROOT / "bench/tests/cpu_run.py"), str(tree), cell]
    if fault:
        args += ["--fault", fault]
    out = subprocess.run(args, cwd=tree, env=_cpu_env(), capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])
