"""Drive one whole benchmark run on the CPU, at a tiny size, for the tests.

    JAX_PLATFORMS=cpu python bench/tests/cpu_run.py <tree> <cell> [--fault <name>] [--trace-off]

``<tree>`` is made by ``tiny_tree``: a copy of ``BENCHMARK.json`` and
``bench/`` whose lakes and mixes are cut to a size a test can hold, with the
program's ``src`` linked in.  The harness's look for a chip and its check
of the chip's backend are stubbed out; everything else is the run the chip
makes, with the named fault of ``bench/control.py`` planted underneath.  Prints the
result's JSON line.  A run of its own process keeps JAX's compile cache
and the planted fault out of the test process.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_LAKES = {"webtable": {"n_tables": 120}}
TINY_MIX = {"rate": 3.0, "rows": [10, 60]}
SECONDS = 4.0
SEED = 3_000_000_017  # beyond 32 bits: a seed may be any whole number


def tiny_tree(dst: Path) -> Path:
    shutil.copytree(REPO / "bench", dst / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    os.symlink(REPO / "src", dst / "src")
    for path in (dst / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["lake"]["params"].update(TINY_LAKES[cfg["lake"]["generator"]])
        path.write_text(json.dumps(cfg))
    for path in (dst / "bench" / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        mix.update(TINY_MIX)
        path.write_text(json.dumps(mix))
    return dst


def main(argv: list[str]) -> None:
    tree, cell = Path(argv[0]), argv[1]
    fault = argv[argv.index("--fault") + 1] if "--fault" in argv else None
    sys.path[:0] = [str(REPO), str(REPO / "src")]
    import jax

    from bench import control, run

    run.devices_or_fail = lambda chips: jax.devices()
    run.check_backend = lambda backend: None
    remove = control.plant(fault) if fault else (lambda: None)
    try:
        result = run.run(cell, SEED, SECONDS, False, root=tree)
    finally:
        remove()
    print(json.dumps(result))


if __name__ == "__main__":
    main(sys.argv[1:])
