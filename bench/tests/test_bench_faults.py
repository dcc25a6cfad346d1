"""Each fault the cells can have, planted underneath a whole run of the
harness at a tiny size on the CPU, makes ``correct`` come out false in
every cell of ``BENCHMARK.json``: a step that returns its state unchanged,
half of each request's candidates left out of the launch, and an answer
altered where it is produced.  (The cells run on one chip: there is no
exchange between chips to leave out.)"""

from __future__ import annotations

import pytest

from bench.tests import cpu_run
from bench.tests.test_bench_correct import CELLS
from bench.tests.test_bench_contract import _tiny_run


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return cpu_run.tiny_tree(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("fault", ["stale", "half_candidates", "altered"])
def test_planted_fault_is_not_correct(tree, fault):
    for cell in CELLS:
        result = _tiny_run(tree, cell, fault=fault)
        assert result["correct"] is False, cell
        assert result["checks"]["wrong_answers"]["value"] > 0, cell
