"""The readers of the program's own spans and counters (``metrics/_program.py``).

A cell declares them by entries alone; a traced CPU run of the tiny churn
cell reads every one.  Against a program that keeps no recorder they read
nothing, and a name the recorder does not hold raises.
"""

import dataclasses
import json
import shutil

import pytest

import cells
import run
from conftest import BENCH, TINY

PROGRAM_METRICS = {
    "lock_wait_ms.fresh": "ms", "admit_wait_ms.fresh": "ms",
    "submit_apply_ms.fresh": "ms", "submit_build_ms.fresh": "ms",
    "submit_stage_ms.fresh": "ms", "inflight_ms.fresh": "ms",
    "ack_lag_ms.fresh": "ms", "h2d_mb.fresh": "MB",
}


def test_declared_program_metrics_read_in_a_traced_run(tmp_path, capsys):
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("tests"))
    b = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    b["per_layer"] += [
        {"name": n, "unit": u, "better": "lower",
         "source": "program_counter" if u == "MB" else "program_span",
         "layer": "serving front end" if n.split("_")[0] in ("lock", "admit", "ack")
         else "host staging", "moves": "freshness_p50_ms", "workloads": ["arxiv.churn-read"]}
        for n, u in PROGRAM_METRICS.items()]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    rc = run.main(["--workload", "arxiv.churn-read", "--seed", str(2**31 + 29), "--seconds", "3",
                   "--trace", "1"], allow_cpu=True, overrides=TINY["arxiv.churn-read"],
                  root=tmp_path)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True, out["compared"]
    for name, unit in PROGRAM_METRICS.items():
        assert out["metrics"][name]["unit"] == unit
        assert out["metrics"][name]["value"] >= 0, name
    assert out["metrics"]["h2d_mb.fresh"]["value"] > 0
    assert out["metrics"]["submit_build_ms.fresh"]["value"] > 0


@dataclasses.dataclass
class _Stats:
    spans: dict
    counters: dict


@dataclasses.dataclass
class _OldStats:  # a program without the recorder
    read_batches: int


@pytest.mark.parametrize("name", sorted(PROGRAM_METRICS))
def test_program_metric_reads_nothing_without_a_recorder(name):
    ctx = {"service_traced": [_OldStats(0), _OldStats(1)]}
    assert cells.reader(name)(ctx) is None


def test_program_metric_means_and_failures():
    before = _Stats({"lp.mutate.lock": (2, 10.0)}, {"engine.h2d_bytes": 0})
    after = _Stats({"lp.mutate.lock": (6, 30.0), "engine.submit": (4, 1.0)},
                   {"engine.h2d_bytes": 8_000_000})
    ctx = {"service_traced": [before, after]}
    assert cells.reader("lock_wait_ms.fresh")(ctx) == 5.0
    assert cells.reader("h2d_mb.fresh")(ctx) == 2.0
    with pytest.raises(KeyError):  # renamed or never recorded: loud
        cells.reader("ack_lag_ms.fresh")(ctx)
    still = {"service_traced": [after, after]}  # nothing happened: nothing read
    assert cells.reader("lock_wait_ms.fresh")(still) is None
