"""Drive one whole benchmark run on the CPU, at a tiny size, for the tests.

    JAX_PLATFORMS=cpu python bench/tests/cpu_run.py <tree> <cell> [--fault <name>] [--trace-off]

``<tree>`` is made by ``tiny_tree``: a copy of ``BENCHMARK.json`` and
``bench/`` whose lakes and mixes are cut to a size a test can hold, each by
the ``TINY`` overrides its generator module declares, with the program's
``src`` linked in.  The harness's look for a chip and its check
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

SECONDS = 4.0
SEED = 3_000_000_017  # beyond 32 bits: a seed may be any whole number


def tiny(generator) -> dict:
    """The ``TINY`` overrides that a lake or traffic generator module declares."""
    if not isinstance(getattr(generator, "TINY", None), dict):
        raise AttributeError(
            f"{generator.__file__} declares no TINY dict of the parameters that "
            "cut it to a size a CPU test can hold"
        )
    return generator.TINY


def tiny_tree(dst: Path, src: Path = REPO) -> Path:
    """Copy ``src``'s ``BENCHMARK.json`` and ``bench/`` to ``dst`` and cut every
    configuration and traffic mix that ``BENCHMARK.json`` names to its
    generator's ``TINY`` size."""
    shutil.copytree(src / "bench", dst / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(src / "BENCHMARK.json", dst / "BENCHMARK.json")
    os.symlink(REPO / "src", dst / "src")
    from bench.catalog import Catalog

    cat = Catalog(dst)
    for entry in cat.spec["configs"]:
        path = dst / entry["file"]
        cfg = json.loads(path.read_text())
        cfg["lake"]["params"].update(tiny(cat.module("lakes", cfg["lake"]["generator"])))
        path.write_text(json.dumps(cfg))
    for name in sorted({cell["traffic"] for cell in cat.spec["workloads"]}):
        path = dst / "bench" / "traffic" / f"{name}.json"
        mix = json.loads(path.read_text())
        mix.update(tiny(cat.module("traffic", mix["generator"])))
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
