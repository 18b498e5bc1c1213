"""The benchmark's own tests: run by hand, on the CPU, not in tier-1.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.fixture
def rehearse(capsys):
    """Run ``run.py --rehearse`` in this process; returns (exit code,
    the last line of standard output parsed, standard error)."""
    import run

    def go(workload, seconds=1.5, trace=0, seed=2147483659):
        code = run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace),
                         "--rehearse"])
        cap = capsys.readouterr()
        lines = cap.out.strip().splitlines()
        return code, (json.loads(lines[-1]) if lines else None), cap.err
    return go
