"""Finds a cell's configuration, traffic mix and metric readers by name.

Nothing here knows a particular cell: ``BENCHMARK.json`` names the files,
``traffic/<name>.json`` holds each mix and ``metrics/<name>.py`` each
per-layer reader (a ``read(ctx)`` that returns a number or None).
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(name: str, root: Path = ROOT) -> dict:
    b = benchmark(root)
    cells = {w["name"]: w for w in b["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in b["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {"name": name, "chips": int(w["chips"]), "config": config, "mix": mix,
            "end_to_end": mine(b["end_to_end"]), "per_layer": mine(b["per_layer"]),
            "peaks": json.loads((root / "bench" / "peaks.json").read_text())}


def reader(metric: str):
    """``read`` of ``metrics/<metric>.py``, else of the file named by the
    metric's name up to its first '.': one reader serves the variants of
    a quantity that move different end-to-end metrics (``solve_ms.fresh``
    and ``solve_ms.rate`` both read ``solve_ms.py``)."""
    path = BENCH / "metrics" / f"{metric}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{metric.split('.', 1)[0]}.py"
    if str(path.parent) not in sys.path:
        sys.path.insert(0, str(path.parent))
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
