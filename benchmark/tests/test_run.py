"""``run.py`` end to end at rehearsal size: the result line of every
cell, the refusals, the peaks table."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT, cells

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _declared(cell, kind):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return {m["name"] for m in bench[kind]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", cells())
def test_result_line_of_every_cell(cell, rehearse):
    code, result, err = rehearse(cell)
    assert code == 0
    # the contract's keys, then the numbers compared, last
    assert set(result) == CONTRACT_KEYS | {"checks"}
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, err
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == _declared(cell, "end_to_end")
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["rehearsal"]
    # every number compared is beside its limit, on stderr too
    for name, c in result["checks"].items():
        assert {"value", "limit"} <= set(c)
        assert f"check {name}:" in err
    assert err.strip().splitlines()[-1] == "correct: True"


@pytest.mark.parametrize("cell", ["session.q6", "session.q14"])
def test_traced_run_reports_the_cells_per_layer_metrics(cell, rehearse):
    code, result, _ = rehearse(cell, seconds=8, trace=1)
    assert code == 0 and result["correct"] is True
    assert set(result) == CONTRACT_KEYS | {"breakdown", "checks"}
    declared = _declared(cell, "per_layer")
    assert set(result["metrics"]) <= declared
    # the scan's readers move scan_query_s, the others' query_s
    assert all(m.endswith(".scan" if cell == "session.q6" else ".session")
               for m in result["metrics"])
    # no peak for a CPU: a share of the roofline is left out, never 0
    assert not any("roofline" in m for m in result["metrics"])
    assert any(m.startswith("compiles_in_window") for m in result["metrics"])
    assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0
    for part in ("device_ops", "idle_gaps"):
        assert 0 < len(result["breakdown"][part]) <= 10


def _run(args, cwd=ROOT, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run([sys.executable] + args, cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=600)


def test_refuses_the_cpu_without_rehearse():
    p = _run(["benchmark/run.py", "--workload", "session.q6", "--seed", "1",
              "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "not 'tpu'" in p.stderr


def test_refuses_a_directory_without_the_program(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["benchmark/run.py", "--workload", "session.q6", "--seed", "1",
              "--seconds", "1", "--trace", "0", "--rehearse"], cwd=tmp_path,
             env={"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_unknown_workload_is_refused():
    p = _run(["benchmark/run.py", "--workload", "no.such", "--seed", "1",
              "--seconds", "1", "--trace", "0", "--rehearse"])
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_peaks_name_the_v5e_and_nothing_is_a_default():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["hbm_gb_per_s"] == 819.0
    assert peaks["TPU v5 lite"]["hbm_gb"] == 16.0
    assert "source" in peaks["TPU v5 lite"]
    assert "cpu" not in peaks
