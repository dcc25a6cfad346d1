"""Finds a cell's parts by name: ``BENCHMARK.json`` names the cells and
metrics, and each configuration, traffic mix, lake generator, query
generator and per-layer metric lives in a file of its own under ``bench/``.
A cell or metric is added by adding files and entries; nothing here lists
them.

A new configuration needs, each as a new file:

- ``bench/configs/<config>.json``: ``name``, ``source``, ``lake``
  (``generator``, ``params``, ``seed``), ``bits``, ``serving``,
  ``guarantees``, ``reduced`` and ``assumed``;
- ``bench/lakes/<generator>.py``, unless a configuration already names it:
  ``generate(params, seed)`` returning a ``bench.lake.Lake``, and ``TINY``,
  the ``params`` overrides that cut the lake to a size a CPU test can hold;
- ``bench/traffic/<mix>.json``: ``generator``, ``rate``, ``seed``, ``vary``
  and the size lists it names (read by ``bench/traffic/__init__.py``);
- ``bench/traffic/<generator>.py``, unless a mix already names it:
  ``query(lake, mix, size, rng)`` returning the key ids and key width, and
  ``TINY``, the mix overrides for a CPU test, with a ``rate`` of 3.0 (the
  tests expect 12 requests in their 4-second runs);
- ``bench/metrics/<metric>.py`` for each new per-layer metric;

and in ``BENCHMARK.json`` its ``configs`` entry, a ``workloads`` entry for
each of its cells, and the ``per_layer`` entries of its new metrics.  The
tests under ``bench/tests/`` then cover it with no file of theirs edited.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


class Catalog:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise FileNotFoundError(f"no {path}")
        self.spec = json.loads(path.read_text())

    def cell(self, name: str) -> dict:
        for cell in self.spec["workloads"]:
            if cell["name"] == name:
                return cell
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for cfg in self.spec["configs"]:
            if cfg["name"] == name:
                return json.loads((self.root / cfg["file"]).read_text())
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        path = self.root / "bench" / "traffic" / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
        return json.loads(path.read_text())

    def end_to_end(self, cell: str) -> list[dict]:
        return [m for m in self.spec["end_to_end"] if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list[dict]:
        return [m for m in self.spec["per_layer"] if cell in m.get("workloads", [cell])]

    def module(self, kind: str, name: str):
        """``bench/<kind>/<name>.py`` of this catalog's tree, imported once."""
        path = self.root / "bench" / kind / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no {kind} module {name!r} ({path})")
        key = f"_bench_{kind}_{name}_{abs(hash(str(path)))}".replace("-", "_")
        if key not in sys.modules:
            spec = importlib.util.spec_from_file_location(key, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[key] = mod
            spec.loader.exec_module(mod)
        return sys.modules[key]
