"""The harness end to end on the CPU at tiny size, its faults and control.

The runs go through ``run.main`` with the chip look switched off from the
test (``allow_cpu``) and tiny sizes passed as overrides; nothing of this
is an option of the command.
"""

import json
import shutil
import subprocess
import sys

import numpy as np
import pytest

import cells
import run
from conftest import BENCH, TINY


def run_cell(capsys, workload, trace=0, seed=2**31 + 7):
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds", "3",
                   "--trace", str(trace)], allow_cpu=True, overrides=TINY[workload])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_no_tpu_exits_nonzero_without_result():
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "arxiv.churn-read", "--seed", "0", "--seconds", "10", "--trace", "0"],
                       capture_output=True, text=True, env={"JAX_PLATFORMS": "cpu",
                                                            "PATH": "/usr/bin:/bin"},
                       cwd=BENCH.parent, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


PRODUCTS_INGEST = {
    "configs": [{"name": "products", "source": "OGB ogbn-products (Hu et al., arXiv:2005.00687)",
                 "file": "bench/configs/products.json",
                 "reduced": ["classes", "rows", "labelled"], "why": "x"}],
    "workloads": [{"name": "products.ingest", "config": "products", "traffic": "ingest",
                   "chips": 1, "why": "x"}],
    "end_to_end": [{"name": "write_ops_per_s", "unit": "ops/s", "better": "higher",
                    "bound": 0.25, "source": "host_clock", "workloads": ["products.ingest"]}],
    "per_layer": [{"name": n, "unit": "ms", "better": "lower", "source": "device_trace",
                   "layer": "x", "moves": "write_ops_per_s", "workloads": ["products.ingest"]}
                  for n in ("host_submit_ms.rate", "solve_ms.rate", "solve_iters.rate",
                            "argkmin_roofline_pct", "device_idle_pct.rate")],
}


INGEST_TINY = {"config": {"rows": 3000, "labelled": 241},
               "mix": {"insert_pool_rows": 16384, "warmup_s": 2, "warmup_quiet_s": 0.5,
                       "warmup_commits": 2, "warmup_max_s": 30,
                       "service": {"window_ops": 128, "window_ms": 50, "max_pending_ops": 256}}}


def _with_products_ingest(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("tests"))
    b = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for key, entries in PRODUCTS_INGEST.items():
        b[key] += entries
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp_path


def test_ingest_cell_added_by_entries_runs_on_cpu(tmp_path, capsys):
    """The closed-loop ingest mix and the products configuration ship as
    files; a cell made of them needs only entries in BENCHMARK.json."""
    root = _with_products_ingest(tmp_path)
    for trace in (0, 1):
        rc = run.main(["--workload", "products.ingest", "--seed", "11", "--seconds", "3",
                       "--trace", str(trace)], allow_cpu=True, overrides=INGEST_TINY, root=root)
        assert rc == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["correct"] is True, out["compared"]
        if trace:
            assert out["metrics"]["solve_iters.rate"]["value"] > 0
        else:
            assert set(out["metrics"]) == {"write_ops_per_s", "setup_s"}


def test_compile_inside_window_fails_the_run(tmp_path, monkeypatch):
    """The tiny ingest cell's rows cross the engine's rungs in its window."""
    import jax

    jax.clear_caches()  # programs an earlier test built would not be built again
    monkeypatch.setattr(run, "COMPILE_FREE_PLATFORMS", ("cpu",))
    b = _with_products_ingest(tmp_path)
    with pytest.raises(RuntimeError, match="inside the measured window"):
        run.main(["--workload", "products.ingest", "--seed", "12", "--seconds", "3",
                  "--trace", "0"], allow_cpu=True, overrides=INGEST_TINY, root=b)


def test_warm_up_leaves_nothing_to_compile_in_window(capsys, monkeypatch):
    """The churn cell's rows stay inside their rungs at this size, so every
    program its window runs (fused read sizes, the supernode step at each
    insert count) has to come from set-up."""
    import jax

    jax.clear_caches()
    monkeypatch.setattr(run, "COMPILE_FREE_PLATFORMS", ("cpu",))
    out = run_cell(capsys, "arxiv.churn-read", seed=99)
    assert out["correct"] is True, out["compared"]


def test_declared_metric_reading_nothing_fails_the_run(capsys, monkeypatch):
    reader = cells.reader
    monkeypatch.setattr(cells, "reader", lambda name: (lambda ctx: None)
                        if name == "host_submit_ms.fresh" else reader(name))
    rc = run.main(["--workload", "arxiv.churn-read", "--seed", "3", "--seconds", "3",
                   "--trace", "1"], allow_cpu=True, overrides=TINY["arxiv.churn-read"])
    assert rc == run.NOTHING_READ
    assert not capsys.readouterr().out.strip()


def test_new_entries_are_found_by_name(tmp_path):
    """A configuration, a mix and a metric added as files plus entries."""
    (tmp_path / "bench").mkdir()
    for part in ("configs", "traffic"):
        shutil.copytree(BENCH / part, tmp_path / "bench" / part)
    shutil.copy(BENCH / "peaks.json", tmp_path / "bench" / "peaks.json")
    b = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    toy = {**json.loads((BENCH / "configs" / "arxiv.json").read_text()), "rows": 999}
    (tmp_path / "bench" / "configs" / "toy.json").write_text(json.dumps(toy))
    (tmp_path / "bench" / "traffic" / "toy-mix.json").write_text(
        json.dumps({"loop": "closed", "marker": 1}))
    b["configs"].append({"name": "toy", "source": "x", "file": "bench/configs/toy.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "toy.toy-mix", "config": "toy", "traffic": "toy-mix",
                           "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "solve_ms.toy", "unit": "ms", "better": "lower",
                           "source": "device_trace", "layer": "propagation",
                           "moves": "setup_s", "workloads": ["toy.toy-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    c = cells.cell("toy.toy-mix", root=tmp_path)
    assert c["config"]["rows"] == 999 and c["mix"]["marker"] == 1
    assert [m["name"] for m in c["per_layer"]] == ["solve_ms.toy"]
    assert [m["name"] for m in c["end_to_end"]] == ["setup_s"]
    # one reader serves each variant of a quantity: solve_ms.toy reads solve_ms.py
    assert cells.reader("solve_ms.toy")({"trace": None, "commit_stats": []}) is None


@pytest.mark.parametrize("workload", sorted(TINY))
def test_cell_runs_correct_on_cpu(capsys, workload):
    out = run_cell(capsys, workload)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in cells.cell(workload)["end_to_end"]}
    assert set(out["metrics"]) == want
    assert list(out)[-1] == "compared"


def test_traced_run_on_cpu(capsys):
    out = run_cell(capsys, "arxiv.churn-read", trace=1)
    assert out["correct"] is True
    assert {"busy_s", "window_s"} <= set(out["device"])
    # the CPU trace has no device plane: device readers stay silent
    assert "solve_ms.fresh" not in out["metrics"]
    assert out["metrics"]["host_submit_ms.fresh"]["value"] > 0


def _solve_returns_f0(monkeypatch):
    from repro.kernels import ops
    from repro.core.propagate import PropagateResult

    def run_propagation(problem, f0, frontier, **kw):
        return PropagateResult(f=f0, iterations=np.int32(0), converged=np.bool_(True),
                               max_residual=np.float32(0))
    monkeypatch.setattr(ops, "run_propagation", run_propagation)


def _half_the_batch(monkeypatch):
    from repro.core.stream import StreamEngine
    import dataclasses

    submit = StreamEngine.submit

    # the deletes and relabels of each window are halved; inserts stay, since
    # a dropped insert shifts the ids of every later row and the run crashes
    # in the program (a relabel of an id past the last row raises) instead
    # of answering wrongly
    def half(self, batch):
        r = len(batch.rel_ids) // 2 if batch.rel_ids is not None else 0
        d = len(batch.del_ids) // 2
        return submit(self, dataclasses.replace(
            batch, del_ids=batch.del_ids[:d], rel_ids=batch.rel_ids[:r],
            rel_labels=batch.rel_labels[:r]))
    monkeypatch.setattr(StreamEngine, "submit", half)


def _read_answer_altered(monkeypatch):
    import dataclasses

    from repro.serving.lp_service import LPService

    serve = LPService._serve_reads

    def altered(self, tickets):
        out = []
        for r in serve(self, tickets):
            pred = r.pred.copy()
            pred[:1] = 1 - np.maximum(pred[:1], 0)
            out.append(dataclasses.replace(r, pred=pred))
        return out
    monkeypatch.setattr(LPService, "_serve_reads", altered)


def _label_altered(monkeypatch):
    from repro.core.stream import StreamEngine

    drain = StreamEngine.drain

    def altered(self):
        st = drain(self)
        if st is not None and len(self._view.f):
            f = self._view.f.copy()
            # alive rows: the oldest rows are deleted during the run
            unl = np.flatnonzero((self._view.labels < 0) & self._view.alive)
            f[unl[: max(1, len(unl) // 20)]] += 0.25
            self._view = type(self._view)(f=f, labels=self._view.labels,
                                          alive=self._view.alive,
                                          commit_id=self._view.commit_id)
        return st
    monkeypatch.setattr(StreamEngine, "drain", altered)


FAULTS = {"state_unchanged": _solve_returns_f0, "half_batch": _half_the_batch,
          "read_altered": _read_answer_altered, "label_altered": _label_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_correct_false(capsys, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    out = run_cell(capsys, "arxiv.churn-read", seed=99)
    assert out["correct"] is False, out["compared"]


@pytest.mark.parametrize("workload", sorted(TINY))
def test_control_fails_a_limit(workload):
    import control

    c = cells.cell(workload)
    c["config"] = {**c["config"], **TINY[workload]["config"]}
    c["mix"] = {**c["mix"], **TINY[workload]["mix"]}
    got = control.readings(c, 5, n_windows=3)
    limits = c["config"]["limits"]
    assert any(got[n] > limits[n] for n in limits if n in got), got
