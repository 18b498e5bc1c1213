"""The per-layer metrics read from the program's time books
(``book_readers.py``): reported by a traced run, absent from an
untraced one, None where the program published nothing; and the PR that
brought them added to ``benchmark/`` without touching what was there."""

import os
import subprocess
from types import SimpleNamespace

import pytest

import book_readers
from conftest import ROOT

QUANTITIES = ("plan_ms", "launch_host_ms", "pump_host_ms", "result_d2h_ms",
              "epilogue_ms", "cached_launches_per_query",
              "books_unaccounted_pct")


def test_traced_run_reports_all_seven_scan_metrics(rehearse):
    code, result, _ = rehearse("session.q6", seconds=4, trace=1)
    assert code == 0 and result["correct"] is True
    got = result["metrics"]
    for q in QUANTITIES:
        assert got[q + ".scan"]["value"] is not None, q
        assert q + ".session" not in got
    assert got["cached_launches_per_query.scan"]["value"] >= 1
    assert got["cached_launches_per_query.scan"]["unit"] == "1/query"
    assert 0 <= got["books_unaccounted_pct.scan"]["value"] < 100
    assert got["plan_ms.scan"]["value"] > 0
    assert got["epilogue_ms.scan"]["value"] > 0


def test_untraced_run_reports_none_of_them(rehearse):
    code, result, _ = rehearse("session.q6", seconds=1.5, trace=0)
    assert code == 0
    assert not any(m.split(".")[0] in QUANTITIES for m in result["metrics"])


def _run(requests, ledgers, monkeypatch):
    monkeypatch.setattr(book_readers, "_recent", lambda: ledgers)
    return {"requests": [SimpleNamespace(t_submit=a, t_done=b)
                         for a, b in requests]}


def _ledger(t0, t1, **over):
    b = {"t0_mono": t0, "t1_mono": t1, "e2e_s": t1 - t0,
         "unaccounted_s": 0.1 * (t1 - t0), "launches": 7, "record_s": 0.002,
         "buckets": {"plan": 0.001, "kernel_launch": 0.004,
                     "kernel_dispatch": 0.010, "pump_idle": 0.006,
                     "result_d2h": 0.003}}
    b.update(over)
    return b


@pytest.mark.parametrize("quantity", QUANTITIES)
def test_readers_return_none_on_an_empty_ring(quantity, monkeypatch):
    run = _run([(0.0, 1.0)], [], monkeypatch)
    assert getattr(book_readers, quantity)(run) is None


def test_readers_take_the_ledgers_inside_answered_requests(monkeypatch):
    run = _run([(10.0, 11.0), (11.0, 12.0)], [
        _ledger(5.0, 5.5, launches=99),          # warm-up: before the window
        _ledger(10.1, 10.9),
        _ledger(11.1, 11.9, launches=9, record_s=None),
        _ledger(11.95, 12.5, launches=99),       # ends after its request
    ], monkeypatch)
    assert len(book_readers.books(run)) == 2
    assert book_readers.cached_launches_per_query(run) == 8
    assert book_readers.plan_ms(run) == pytest.approx(1.0)
    assert book_readers.launch_host_ms(run) == pytest.approx(4.0)
    assert book_readers.pump_host_ms(run) == pytest.approx(16.0)
    assert book_readers.result_d2h_ms(run) == pytest.approx(3.0)
    assert book_readers.epilogue_ms(run) == pytest.approx(2.0)
    assert book_readers.books_unaccounted_pct(run) == pytest.approx(10.0)


def test_a_program_without_the_bucket_reports_nothing(monkeypatch):
    old = _ledger(10.1, 10.9)
    del old["buckets"]["plan"], old["launches"], old["record_s"]
    run = _run([(10.0, 11.0)], [old], monkeypatch)
    assert book_readers.plan_ms(run) is None
    assert book_readers.cached_launches_per_query(run) is None
    assert book_readers.epilogue_ms(run) is None
    assert book_readers.pump_host_ms(run) == pytest.approx(16.0)


def test_a_program_without_the_ring_reports_nothing(monkeypatch):
    from spark_rapids_tpu.runtime import attribution
    monkeypatch.delattr(attribution, "recent")
    assert book_readers._recent() == []


def test_the_pr_only_added_to_the_benchmark():
    """Against the commit that accepted the benchmark as it was: files
    under ``benchmark/`` are new, and ``BENCHMARK.json`` only grew at the
    end of ``per_layer``."""
    base = "94af29e70d4c1a58afff57f2774ae13423417e82"
    git = lambda *a: subprocess.run(("git",) + a, cwd=ROOT, text=True,
                                    capture_output=True)
    if git("cat-file", "-e", base).returncode:
        pytest.skip("not a checkout with the history")
    status = git("diff", "--name-status", base, "--", "benchmark").stdout
    changed = [line.split("\t") for line in status.splitlines()]
    assert changed and all(s == "A" for s, _ in changed), changed
    import json
    was = json.loads(git("show", base + ":BENCHMARK.json").stdout)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        now = json.load(f)
    for key, old in was.items():
        if isinstance(old, list):
            assert now[key][:len(old)] == old, key
        else:
            assert now[key] == old, key
    added = now["per_layer"][len(was["per_layer"]):]
    assert {m["name"].rsplit(".", 1)[0] for m in added} == set(QUANTITIES)
    assert all(len(now[k]) == len(was[k])
               for k in ("configs", "workloads", "end_to_end"))
