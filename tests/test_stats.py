"""The stats plane: per-operator runtime statistics, EXPLAIN ANALYZE,
the persistent profile store, and the regression-diff profiler CLI.

Covers the full chain: collection at the auto-wrapped pump boundary →
per-partition exchange counts (+ cluster merge) → AQE consuming the
recorded counts → `df.explain("analyze")` / `session.last_query_profile`
→ JSONL profile store with stable plan signatures → `utils/profile.py`
reports and the diff gate's nonzero-exit verdict.
"""

import json
import multiprocessing as mp
import os
import socket
import subprocess
import sys
import traceback

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.runtime import stats
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.column import col
from spark_rapids_tpu.utils.datagen import SkewedLongGen, skewed_null_table
from spark_rapids_tpu.utils.harness import tpu_session

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lineitem(n=4000, seed=3):
    rng = np.random.default_rng(seed)
    return pa.table({
        "l_returnflag": pa.array(rng.integers(0, 2, n)),
        "l_linestatus": pa.array(rng.integers(0, 2, n)),
        "l_quantity": pa.array(rng.uniform(1, 50, n)),
        "l_extendedprice": pa.array(rng.uniform(1, 1e5, n)),
    })


def _q1ish(s, t):
    return (s.createDataFrame(t)
            .groupBy("l_returnflag", "l_linestatus")
            .agg(F.sum("l_quantity").alias("sum_qty"),
                 F.sum("l_extendedprice").alias("sum_price"),
                 F.avg("l_quantity").alias("avg_qty"),
                 F.count("*").alias("cnt")))


# ---------------------------------------------------------------------------
# collection primitives
# ---------------------------------------------------------------------------

def test_skew_factor_and_merge():
    assert stats.skew_factor([]) == 1.0
    assert stats.skew_factor([0, 0, 0]) == 1.0
    assert stats.skew_factor([5, 5, 5, 5]) == 1.0
    assert stats.skew_factor([100, 1, 1, 1]) == pytest.approx(
        100 / 25.75)
    # coordinator-side merge: element-wise sum across executors
    assert stats.merge_partition_counts(
        [[10, 0, 2], [5, 1, 3]]) == [15, 1, 5]
    with pytest.raises(ValueError, match="disagree on width"):
        stats.merge_partition_counts([[1, 2], [1, 2, 3]])


def test_hist_buckets():
    assert stats._hist_bucket(0) == "0"
    assert stats._hist_bucket(1) == "1"
    assert stats._hist_bucket(2) == "2-2"
    assert stats._hist_bucket(3) == "3-4"
    assert stats._hist_bucket(1000) == "513-1024"


def test_plan_signature_is_stable_and_positional():
    schema = T.StructType((T.StructField("a", T.LongT, False),))
    s1 = stats.plan_signature("TpuScanExec", "0.1", schema)
    assert s1 == stats.plan_signature("TpuScanExec", "0.1", schema)
    assert s1 != stats.plan_signature("TpuScanExec", "0.0", schema)
    assert s1 != stats.plan_signature("TpuProjectExec", "0.1", schema)


def test_nested_query_rides_owner_collector():
    """Ownership is by thread of execution: nested on the owner's
    thread rides it, another thread gets a collector of its own, an
    unowned thread has none."""
    import threading
    st = stats.start_query(1)
    try:
        assert stats.start_query(2) is None  # nested: owner keeps it
        assert stats.current() is st
        seen = []

        def other():
            seen.append(stats.current())     # nobody bound this thread
            mine = stats.start_query(3)
            seen.append(mine)
            seen.append(stats.current())
            stats.end_query(mine)
            seen.append(stats.current())
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        assert seen[0] is None and seen[3] is None
        assert seen[1] is not None and seen[1] is not st
        assert seen[2] is seen[1] and seen[1].query_id == 3
        assert stats.current() is st
    finally:
        stats.end_query(st)
    assert stats.current() is None


# ---------------------------------------------------------------------------
# explain("analyze") + last_query_profile (the tentpole's human surface)
# ---------------------------------------------------------------------------

def test_explain_analyze_q1_style_aggregation(capsys):
    """Every operator of a q1-style aggregation shows observed rows,
    bytes, batch count, and (traced) self-time."""
    s = tpu_session({"spark.rapids.tpu.stats.enabled": True,
                     "spark.rapids.sql.trace.enabled": True})
    df = _q1ish(s, _lineitem())
    df.toArrow()
    df.explain("analyze")
    out = capsys.readouterr().out
    plan_lines = [ln for ln in out.splitlines() if "[rows=" in ln]
    assert len(plan_lines) >= 3  # scan, agg, D2H at minimum
    for ln in plan_lines:
        assert "batches=" in ln and "bytes=" in ln and "self=" in ln, ln
    assert "wall" in out

    prof = s.last_query_profile()
    assert prof is not None and prof["ops"]
    scan = next(r for r in prof["ops"] if r["op"] == "TpuScanExec")
    assert scan["rows_out"] == 4000
    assert scan["batches_out"] >= 1
    assert scan["bytes_out"] > 0
    assert scan["self_s"] is not None
    assert scan["batch_rows_hist"]
    root = prof["ops"][0]
    assert root["path"] == "0"
    assert root["rows_in"] == sum(
        r["rows_out"] for r in prof["ops"] if r["path"] == "0.0")


def test_explain_analyze_executes_when_needed(capsys):
    """explain("analyze") on a never-executed frame runs the query
    itself (temporarily forcing stats+trace on) and restores the confs."""
    s = tpu_session()
    s.conf.set("spark.rapids.sql.trace.enabled", False)
    df = _q1ish(s, _lineitem(500))
    df.explain("analyze")
    out = capsys.readouterr().out
    assert "rows=" in out and "self=" in out
    assert s.conf.get("spark.rapids.sql.trace.enabled") is False
    assert s.last_query_profile() is not None


def test_zero_row_query_produces_zeroed_stats():
    """Empty-batch / zero-row operators produce valid (zeroed) stats
    records, not crashes or holes (satellite: empty-input regression)."""
    s = tpu_session({"spark.rapids.tpu.stats.enabled": True})
    df = (s.createDataFrame(_lineitem(300))
          .filter(col("l_quantity") > 1e18)  # selects nothing
          .groupBy("l_returnflag")
          .agg(F.sum("l_quantity").alias("sq")))
    out = df.toArrow()
    assert out.num_rows == 0
    prof = s.last_query_profile()
    assert prof is not None
    for rec in prof["ops"]:
        assert rec["rows_out"] == 0 or rec["op"] == "TpuScanExec", rec
        assert rec["rows_out"] >= 0 and rec["bytes_out"] >= 0
        assert isinstance(rec["batch_rows_hist"], dict)


def test_stats_off_by_default_records_nothing():
    s = tpu_session()  # stats.enabled defaults to off (per-batch sync)
    df = _q1ish(s, _lineitem(500))
    df.toArrow()
    assert s.last_query_profile() is None
    assert "op_stats" not in df._last_query_entry


# ---------------------------------------------------------------------------
# exchange skew (satellites: skewed datagen + skew stats + AQE wiring)
# ---------------------------------------------------------------------------

def test_skewed_exchange_reports_skew_factor():
    """A hash exchange over the skewed generator's hot key reports a
    skew factor above the conf threshold and flags skewed=True."""
    t = skewed_null_table(6000, seed=2, hot_mass=0.9)
    s = tpu_session({"spark.rapids.tpu.stats.enabled": True,
                     "spark.rapids.tpu.stats.skewThreshold": 2.0})
    df = s.createDataFrame(t).repartition(8, "k")
    df.toArrow()
    prof = s.last_query_profile()
    assert prof["exchanges"], "no exchange stats recorded"
    ex = prof["exchanges"][0]
    assert ex["partitions"] == 8
    assert ex["skew_factor"] > 2.0
    assert ex["skewed"] is True
    assert ex["total"] > 0
    # the per-op record carries the raw per-partition sizes too
    rec = next(r for r in prof["ops"] if r["sig"] == ex["sig"])
    sizes = rec.get("partition_rows") or rec.get("partition_bytes")
    assert len(sizes) == 8 and max(sizes) == ex["max"]


def test_skewed_gen_shape():
    g = SkewedLongGen(hot_mass=0.9, nullable=False)
    rng = np.random.default_rng(0)
    vals = np.array(g.generate_values(rng, 10_000))
    frac0 = float((vals == 0).mean())
    assert 0.85 < frac0 < 0.95  # hot key carries ~hot_mass of the rows
    t = skewed_null_table(2000, seed=0, null_ratio=0.4)
    assert t.column_names == ["k", "v", "s"]
    assert t.column("k").null_count == 0
    assert 0.3 < t.column("v").null_count / 2000 < 0.5


def test_full_level_records_null_ratio():
    t = skewed_null_table(3000, seed=4, null_ratio=0.4)
    s = tpu_session({"spark.rapids.tpu.stats.enabled": True,
                     "spark.rapids.tpu.stats.level": "FULL"})
    s.createDataFrame(t).repartition(4, "k").toArrow()
    prof = s.last_query_profile()
    assert prof["level"] == "FULL"
    recs = [r for r in prof["ops"] if r.get("null_ratio")]
    assert recs, "no null ratios recorded at level=FULL"
    nr = recs[0]["null_ratio"]
    assert nr["k"] == 0.0
    assert 0.3 < nr["v"] < 0.5


def test_aqe_prefers_recorded_partition_counts():
    """The shaped-read planner consults the collector's recorded counts
    before paying for a fresh device count (satellite: AQE wiring)."""
    from spark_rapids_tpu.exec.aqe import TpuAQEShuffleReadExec
    from spark_rapids_tpu.exec.base import TpuExec

    schema = T.StructType((T.StructField("a", T.LongT, False),))

    class _StubExchange(TpuExec):
        def num_partitions(self):
            return 4

        def aqe_partition_stats(self):
            raise AssertionError(
                "planner measured the exchange despite recorded stats")

    stub = _StubExchange(schema)
    st = stats.start_query(777)
    assert st is not None
    try:
        st.record_partitions(stub, [100, 1, 1, 1], unit="rows")
        reader = TpuAQEShuffleReadExec(stub, target_bytes=800,
                                       row_bytes=8)  # target = 100 rows
        specs = reader._plan()  # would raise if it re-measured
    finally:
        stats.end_query(st)
    # partition 0 read alone, the three 1-row tails coalesced
    assert ("range", 0, 1) in specs
    assert ("range", 1, 4) in specs


# ---------------------------------------------------------------------------
# the profile store (persistent, stable signatures)
# ---------------------------------------------------------------------------

def test_profile_store_appends_with_stable_signatures(tmp_path):
    store = str(tmp_path / "profiles.jsonl")
    t = _lineitem(800)
    for _ in range(2):  # two sessions, same logical plan
        s = tpu_session({"spark.rapids.tpu.stats.enabled": True,
                         "spark.rapids.tpu.stats.storePath": store})
        _q1ish(s, t).toArrow()
    recs = stats.load_profiles(store)
    assert len(recs) == 2
    sigs0 = [(o["op"], o["sig"], o["path"]) for o in recs[0]["ops"]]
    sigs1 = [(o["op"], o["sig"], o["path"]) for o in recs[1]["ops"]]
    assert sigs0 == sigs1  # cross-run diffable
    assert recs[0]["record"] == "profile"
    assert recs[0]["status"] == "ok"


def test_load_profiles_skips_torn_lines(tmp_path):
    p = tmp_path / "store.jsonl"
    good = {"record": "profile", "ops": []}
    p.write_text(json.dumps(good) + "\n{torn\n" + json.dumps(good) + "\n")
    assert len(stats.load_profiles(str(p))) == 2


# ---------------------------------------------------------------------------
# profiler CLI (satellite: diff gate)
# ---------------------------------------------------------------------------

def _fake_profile(agg_self=0.2):
    return {"record": "profile", "version": 1, "query_id": 1,
            "level": "BASIC", "skew_threshold": 2.0, "wall_s": 1.0,
            "ops": [
                {"op": "TpuScanExec", "sig": "aaa", "path": "0",
                 "rows_out": 10, "self_s": 0.1, "total_s": 0.1},
                {"op": "TpuHashAggregateExec", "sig": "bbb",
                 "path": "0.0", "rows_out": 3, "self_s": agg_self,
                 "total_s": agg_self + 0.1}],
            "exchanges": [
                {"op": "TpuShuffleExchangeExec", "sig": "ccc",
                 "path": "0.1", "unit": "rows", "partitions": 4,
                 "max": 90, "total": 100, "skew_factor": 3.6,
                 "skewed": True, "executors": 1}]}


def _write_store(path, record):
    with open(path, "w") as f:
        f.write(json.dumps(record) + "\n")


def test_profile_cli_diff_detects_regression(tmp_path):
    """Injected 2x self-time regression → nonzero exit, offending op
    named in the output; identical runs → exit 0."""
    a = str(tmp_path / "a.jsonl")
    b = str(tmp_path / "b.jsonl")
    _write_store(a, _fake_profile(agg_self=0.2))
    _write_store(b, _fake_profile(agg_self=0.4))
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    run = [sys.executable, "-m", "spark_rapids_tpu.utils.profile"]
    r = subprocess.run(run + ["diff", a, b], capture_output=True,
                       text=True, env=env, cwd=REPO_ROOT)
    assert r.returncode == 2, r.stdout + r.stderr
    assert "REGRESSION" in r.stdout
    assert "TpuHashAggregateExec" in r.stdout
    same = subprocess.run(run + ["diff", a, a], capture_output=True,
                          text=True, env=env, cwd=REPO_ROOT)
    assert same.returncode == 0, same.stdout + same.stderr


def _run_with(self_s):
    return [{"label": "q", "ops": {"x": {"op": "x", "self_s": self_s,
                                         "total_s": self_s}},
             "exchanges": [], "compiles": None, "wall_s": None}]


def test_profile_cli_diff_thresholds():
    from spark_rapids_tpu.utils import profile as P
    a = _run_with(0.1)
    # below the ratio threshold: clean
    _, regs = P.diff_runs(a, _run_with(0.14), threshold=1.5)
    assert regs == []
    # at/over the threshold: regression with the exact ratio
    _, regs = P.diff_runs(a, _run_with(0.25), threshold=2.0)
    assert len(regs) == 1 and regs[0]["ratio"] == 2.5
    # absolute floor: microsecond ops never fail the gate even at 100x
    _, regs = P.diff_runs(_run_with(1e-6), _run_with(1e-4),
                          threshold=1.5)
    assert regs == []
    # vanished baseline: inf ratio still counts as a regression
    _, regs = P.diff_runs(_run_with(0.0), _run_with(0.1), threshold=1.5)
    assert len(regs) == 1


def test_profile_cli_reports(tmp_path, capsys):
    from spark_rapids_tpu.utils import profile as P
    store = str(tmp_path / "s.jsonl")
    _write_store(store, _fake_profile())
    assert P.main(["top", store, "--n", "5"]) == 0
    out = capsys.readouterr().out
    assert "TpuHashAggregateExec[bbb]" in out
    assert P.main(["skew", store]) == 0
    out = capsys.readouterr().out
    assert "SKEWED" in out and "skew=3.60" in out
    assert P.main(["storms", store]) == 0  # no compile telemetry: noted
    assert "no compile telemetry" in capsys.readouterr().out


def test_profile_cli_reads_event_log(tmp_path, capsys):
    """The CLI consumes the query event log directly — rollup self-times
    and compile telemetry."""
    from spark_rapids_tpu.utils import profile as P
    log = str(tmp_path / "qlog.jsonl")
    entry = {"query_id": 5, "status": "ok", "plan": "x", "wall_s": 2.0,
             "op_rollup": {"TpuScanExec": {"self_s": 1.5, "total_s": 1.5,
                                           "spans": 3}},
             "telemetry": {"tpuq_kernel_compile_total": 70},
             "health": [{"severity": "WARN", "check": "compile_storm",
                         "value": 70, "threshold": 64,
                         "detail": "70 XLA compiles in one query"}]}
    with open(log, "w") as f:
        f.write(json.dumps(entry) + "\n")
    runs = P.load_runs(log)
    assert runs[0]["compiles"] == 70
    assert P.main(["storms", log]) == 0
    out = capsys.readouterr().out
    assert "70 kernel compiles" in out and "WARN" in out
    assert P.main(["top", log]) == 0
    assert "TpuScanExec" in capsys.readouterr().out


def test_profile_cli_bad_input(tmp_path):
    from spark_rapids_tpu.utils import profile as P
    p = tmp_path / "junk.jsonl"
    p.write_text('{"neither": 1}\n')
    with pytest.raises(SystemExit) as e:
        P.main(["top", str(p)])
    assert e.value.code == 1


# ---------------------------------------------------------------------------
# docs + lint gates (satellites: field catalog, documented confs)
# ---------------------------------------------------------------------------

def test_stats_fields_documented():
    from spark_rapids_tpu.utils.docs_gen import check_stats_documented
    assert check_stats_documented() == []


def test_stats_confs_registered():
    from spark_rapids_tpu import conf as C
    for key in ("spark.rapids.tpu.stats.enabled",
                "spark.rapids.tpu.stats.level",
                "spark.rapids.tpu.stats.storePath",
                "spark.rapids.tpu.stats.skewThreshold"):
        assert key in C.REGISTRY.entries, key
    with pytest.raises(ValueError):
        C.STATS_LEVEL.convert("VERBOSE")
    with pytest.raises(ValueError):
        C.STATS_SKEW_THRESHOLD.convert("1.0")


# ---------------------------------------------------------------------------
# cluster-wide merge: multi-executor ICI exchange
# ---------------------------------------------------------------------------

def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_MP_UNSUPPORTED = "Multiprocess computations aren't implemented"


_STATS_PARALLELISM = 8


def _stats_table():
    rng = np.random.default_rng(5)
    n = 20_000
    # hot-headed key: 85% of the INPUT rows carry key 7
    k = np.where(rng.random(n) < 0.85, 7, rng.integers(0, 500, n))
    return pa.table({"k": pa.array(k),
                     "v": pa.array(rng.integers(-100, 100, n))})


def _stats_worker(pid, nprocs, jax_port, rdv_addr, q):
    try:
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_enable_x64", True)
        from spark_rapids_tpu.sql import functions as F
        from spark_rapids_tpu.sql.session import TpuSession

        s = TpuSession({
            "spark.rapids.sql.enabled": True,
            "spark.rapids.tpu.stats.enabled": True,
            "spark.rapids.shuffle.mode": "ICI",
            "spark.default.parallelism": _STATS_PARALLELISM,
            "spark.rapids.executor.id": pid,
            "spark.rapids.executor.count": nprocs,
            "spark.rapids.executor.coordinator.address":
                f"127.0.0.1:{jax_port}",
            "spark.rapids.shuffle.rendezvous.address": rdv_addr,
            "spark.rapids.shuffle.rendezvous.timeoutSec": 120.0,
        })
        t = _stats_table()
        (s.createDataFrame(t).groupBy("k")
         .agg(F.sum("v").alias("sv")).toArrow())
        prof = s.last_query_profile()
        q.put(("ok", pid, prof["exchanges"]))
    except Exception:  # pragma: no cover
        tb = traceback.format_exc()
        q.put(("skip" if _MP_UNSUPPORTED in tb else "err", pid, tb))


@pytest.mark.distributed(timeout=420)
def test_multiprocess_exchange_merges_cluster_wide_counts():
    """Each executor's per-partition counts ride the rendezvous
    allgather; EVERY process's profile shows the cluster-wide totals and
    the cluster-wide skew factor (the tentpole's coordinator merge)."""
    from spark_rapids_tpu.parallel.rendezvous import RendezvousCoordinator
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    nprocs = 2
    jax_port = _free_port()
    coord = RendezvousCoordinator(num_processes=nprocs)
    procs = [ctx.Process(target=_stats_worker,
                         args=(i, nprocs, jax_port, coord.address, q))
             for i in range(nprocs)]
    for p in procs:
        p.start()
    results = []
    try:
        for _ in range(nprocs):
            results.append(q.get(timeout=360))
    finally:
        for p in procs:
            p.join(timeout=60)
            if p.is_alive():
                p.terminate()
        coord.shutdown()
    errs = [r for r in results if r[0] == "err"]
    assert not errs, errs[0][2]
    if any(r[0] == "skip" for r in results):
        pytest.skip("XLA CPU backend in this jaxlib build cannot run "
                    "cross-process computations")
    exchanges = [r[2] for r in sorted(results, key=lambda r: r[1])]
    assert all(ex for ex in exchanges), exchanges
    ex0, ex1 = exchanges[0][0], exchanges[1][0]
    # merged at the rendezvous: both processes see the SAME cluster view
    assert ex0["executors"] == nprocs
    # The exchange sits BELOW the partial aggregate, so what crosses it
    # is each scan slice's groups, not the 20 000 input rows: the merge
    # sums exactly what every executor sent, each slice counted once
    # (slices alternate between the executors).
    from spark_rapids_tpu.exec.basic import _slice_table
    sent = sum(len(set(sl.column("k").to_pylist()))
               for sl in _slice_table(_stats_table(), _STATS_PARALLELISM))
    assert ex0["total"] == ex1["total"] == sent
    assert ex0["max"] == ex1["max"]
    assert ex0["skew_factor"] == ex1["skew_factor"]
    # ... and the hot key has folded to one row per slice by then: the
    # cluster-wide view shows a balanced exchange
    assert ex0["skew_factor"] == pytest.approx(
        ex0["max"] / (sent / ex0["partitions"]), rel=1e-3)
    assert not ex0["skewed"]
