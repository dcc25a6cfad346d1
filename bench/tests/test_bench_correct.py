"""``correct``: every cell's run comes out correct at a tiny size on the CPU,
and the control (exact verification skipped) and each planted fault come
out not correct.  The harness's look for a chip is skipped; the rest of a
run is the one the chip makes."""

from __future__ import annotations

import pytest

from bench.tests import cpu_run
from bench.tests.test_bench_contract import E2E, SPEC, _tiny_run

CELLS = [cell["name"] for cell in SPEC["workloads"]]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return cpu_run.tiny_tree(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tree, cell):
    result = _tiny_run(tree, cell)
    assert list(result)[:2] == ["correct", "attempted"] and list(result)[-1] == "checks"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 12
    assert set(result["metrics"]) == E2E
    assert all(isinstance(m["value"], float) and m["value"] > 0 for m in result["metrics"].values())
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert result["checks"] == {"wrong_answers": {"value": 0, "limit": 0},
                                "missing_answers": {"value": 0, "limit": 0}}


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tree, cell):
    result = _tiny_run(tree, cell, fault="unverified")
    assert result["correct"] is False
    assert result["checks"]["wrong_answers"]["value"] > 0
