"""A later PR adds a configuration, a traffic mix, a cell and metrics
as files and entries only.  Shown on the cell this benchmark does not
have yet: TPC-H's throughput test through ``QueryServer``
(``data/added/``: a proposal, rehearsed here, never run on the chip).
A copy of ``BENCHMARK.json`` and ``benchmark/`` gets the new files laid
over it and the new entries appended; no file that was there changes,
and the rehearsal runs the new cell and reports the new metrics."""

import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, HERE, ROOT

ADDED = os.path.join(HERE, "data", "added")
CELL = "server.throughput"


def _files(top):
    return sorted(os.path.relpath(os.path.join(d, f), top)
                  for d, _, fs in os.walk(top) for f in fs
                  if "__pycache__" not in d)


def test_a_served_cell_added_as_files_and_entries_only(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "spark_rapids_tpu"),
               tmp_path / "spark_rapids_tpu")
    before = _files(tmp_path / "benchmark")
    with open(os.path.join(ADDED, "BENCHMARK.entries.json")) as f:
        entries = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for kind, new in entries.items():
        assert not {e["name"] for e in new} & {e["name"] for e in bench[kind]}
        bench[kind] = bench[kind] + new
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    new_files = [p for p in _files(ADDED) if p != "BENCHMARK.entries.json"]
    assert not set(new_files) & set(before)
    for rel in new_files:
        shutil.copy(os.path.join(ADDED, rel), tmp_path / "benchmark" / rel)

    def rehearse(trace):
        p = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
             "2147483659", "--seconds", "4", "--trace", str(trace),
             "--rehearse"], cwd=tmp_path, capture_output=True, text=True,
            timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert p.returncode == 0, p.stderr[-2000:]
        lines = p.stdout.strip().splitlines()
        return json.loads(lines[-1]), [json.loads(x) for x in lines[:-1]]

    result, phases = rehearse(0)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s", "served_query_s",
                                      "served_q6_p95_s"}
    window = next(p for p in phases if p["phase"] == "window")
    # both streams went through the three queries pass after pass, and
    # only whole passes are counted
    assert set(window["by_query"]) == {"q1", "q6", "q12"}
    assert len(set(window["by_query"].values())) == 1
    result, _ = rehearse(1)
    assert result["correct"] is True
    assert {"queue_wait_ms.server", "compiles_in_window.server"} <= set(
        result["metrics"])
