"""chip_smoke.py on the CPU, and the pieces it leans on that must not
hide the device: compile-cache placement, HBM budget detection, the
native library build, the multi-device concat; and that the smoke
stands on benchmark/'s generator, builders, counters and comparison."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(*args, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run([sys.executable, SMOKE, *args], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    lines = [json.loads(ln) for ln in r.stdout.splitlines() if ln.strip()]
    return r, lines


def _same(cmp_line):
    """A ``compare`` line carries ``compare.compare_tables``' own
    numbers; the smoke holds them to 0 and to its ``rtol``."""
    return (cmp_line["exact_mismatches"] == 0
            and cmp_line["max_rel_err"] <= cmp_line["rtol"])


@pytest.fixture(scope="module")
def rehearsal():
    return _run_smoke("--rehearse")


def test_rehearse_runs_every_phase_on_cpu(rehearsal):
    r, lines = rehearsal
    assert r.returncode == 0, r.stderr[-2000:]
    assert lines[-1] == {"ok": True, "device": lines[-1]["device"]}
    assert set(lines[-1]["device"]) == {"platform", "kind", "count"}
    assert lines[-1]["device"]["platform"] == "cpu"
    phases = [ln["phase"] for ln in lines[:-1]]
    assert phases[:2] == ["device", "data"]
    for want in ("direct", "served", "kernels", "compare", "summary"):
        assert want in phases, phases
    direct = {ln["query"]: ln for ln in lines[:-1]
              if ln["phase"] == "direct"}
    assert list(direct) == ["q6", "q1", "q12"]
    for rec in direct.values():
        assert rec["fallback_ops"] == 0 and rec["warm_equals_cold"]
        assert rec["kernel_compiles_warm"] == 0
        assert rec["xla_compiles_warm"] == 0
    compares = [ln for ln in lines if ln.get("phase") == "compare"]
    assert len(compares) == 6 and all(_same(c) for c in compares)
    assert lines[0]["compile_cache_dir"] is None  # off on the CPU
    # submitted together, each served query closed a ledger of its own
    served = [ln for ln in lines if ln.get("phase") == "served"
              and "query" in ln]
    assert [ln["query"] for ln in served] == ["q6", "q1", "q12"]
    assert all(ln["books"] == 1 and "queue_wait" in ln["buckets"]
               for ln in served)


def test_rehearsal_runs_the_cells_data_and_queries(rehearsal):
    """The smoke and the cells of BENCHMARK.json share one generator and
    one set of builders: the relations are ``tpch_gen.RELATIONS`` and
    every query phase names q6, q1, q12 with a binding its module drew."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import tpch_gen
    _, lines = rehearsal
    data = next(ln for ln in lines if ln.get("phase") == "data")
    assert list(data["rows"]) == list(tpch_gen.RELATIONS)
    assert data["rows"] == tpch_gen.cardinalities(data["sf"])
    for phase, n in (("binding", 1), ("direct", 1), ("compare", 2)):
        assert [ln["query"] for ln in lines if ln.get("phase") == phase
                ] == [q for q in ("q6", "q1", "q12") for _ in range(n)]
    bound = {ln["query"]: ln for ln in lines if ln.get("phase") == "binding"}
    assert set(bound["q6"]) >= {"year", "discount", "quantity"}
    assert set(bound["q12"]) >= {"shipmode1", "shipmode2", "year"}


def test_smoke_defines_no_copy_of_the_benchmarks_code():
    sys.path.insert(0, REPO)
    import chip_smoke
    for name in ("XlaCounts", "compare_tables", "delta", "gen_tpch",
                 "TPCH_BUILDERS"):
        assert not hasattr(chip_smoke, name), name
    bench_dir = os.path.join(REPO, "benchmark")
    for mod in (chip_smoke.counters, chip_smoke.compare, chip_smoke.tpch_gen):
        assert os.path.dirname(mod.__file__) == bench_dir


def test_nothing_sends_a_reader_to_the_old_scoreboard():
    """The old scoreboard and its records are gone (PR 31): no tracked
    source file, doc or README names them; the history (CHANGES,
    ROADMAP, PERF) and the benchmark's own files may."""
    r = subprocess.run(["git", "ls-files", "-z"], cwd=REPO,
                       capture_output=True, text=True)
    if r.returncode != 0:
        pytest.skip("not a git checkout: no list of tracked files")
    gone = ("bench" + ".py", "BENCH" + "_r", "import " + "bench")
    hits = []
    for rel in filter(None, r.stdout.split("\0")):
        if rel.startswith("benchmark/") or not (
                rel.endswith(".py") or rel == "README.md"
                or (rel.startswith("docs/") and rel.endswith(".md"))):
            continue
        path = os.path.join(REPO, rel)
        if not os.path.exists(path):  # deleted, not yet committed
            continue
        with open(path, errors="replace") as fh:
            text = fh.read()
        hits += [f"{rel}: {g}" for g in gone if g in text]
    assert not hits, hits
    assert not os.path.exists(os.path.join(REPO, "bench" + ".py"))


def test_refuses_cpu_without_rehearse():
    r, lines = _run_smoke()
    assert r.returncode != 0
    assert lines[-1]["ok"] is False
    assert "not 'tpu'" in lines[-1]["error"]
    assert not any(ln.get("ok") for ln in lines)


def test_refuses_small_scale_without_rehearse():
    r, lines = _run_smoke("--sf", "0.01")
    assert r.returncode != 0 and lines[-1]["ok"] is False


def test_rehearse_four_devices_runs_only_the_ici_path():
    r, lines = _run_smoke("--rehearse", "--chips", "4", XLA_FLAGS="")
    assert r.returncode == 0, r.stderr[-2000:]
    assert lines[-1]["ok"] is True and lines[-1]["device"]["count"] == 4
    phases = [ln["phase"] for ln in lines[:-1]]
    assert "direct" not in phases and "served" not in phases
    assert phases.count("ici") == 1 and phases.count("one_device") == 1
    placed = next(ln for ln in lines if ln.get("phase") == "ici_placement")
    assert placed["ici_exchanges_executed"] >= 1
    assert sum(1 for b in placed["live_array_bytes"] if b) == 4
    cmp_ = next(ln for ln in lines if ln.get("phase") == "compare")
    assert cmp_["against"] == "one_device" and _same(cmp_)


# -- compile-cache placement: one function decides ---------------------------

def test_cache_dir_env_wins_and_touches_nothing(tmp_path, monkeypatch):
    from spark_rapids_tpu.runtime import device
    placed = tmp_path / "placed"
    placed.mkdir()
    (placed / "someone-elses-entry").write_text("keep me")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    assert device.compile_cache_dir() == (str(placed), "env")
    # the conf key is outranked, with no sub-directory either
    assert device.compile_cache_dir(str(tmp_path / "conf")) == (
        str(placed), "env")
    assert sorted(os.listdir(tmp_path)) == ["placed"]
    assert os.listdir(placed) == ["someone-elses-entry"]


def test_cache_dir_conf_keeps_fingerprint_subdir(tmp_path, monkeypatch):
    from spark_rapids_tpu.runtime import device
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path, origin = device.compile_cache_dir(str(tmp_path))
    assert origin == "conf"
    assert os.path.dirname(path) == str(tmp_path)
    assert os.path.basename(path) == device._machine_fingerprint()
    assert not os.listdir(tmp_path)  # choosing is pure


def test_cache_dir_default_is_fixed_inside_checkout(monkeypatch):
    from spark_rapids_tpu.runtime import device
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = device.compile_cache_dir()
    assert first == device.compile_cache_dir()
    assert first == (os.path.join(REPO, ".jax_cache"), "checkout")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    code = ("from spark_rapids_tpu.runtime import device; "
            "print(device.compile_cache_dir()[0])")
    outs = {subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                           capture_output=True, text=True,
                           check=True).stdout.strip() for _ in range(2)}
    assert outs == {first[0]}


def test_cache_stays_off_on_cpu_even_when_placed(tmp_path):
    """On the CPU backend nothing is cached, wherever the cache was
    placed (a subprocess: the switch is process-global)."""
    code = ("import jax; "
            "from spark_rapids_tpu.runtime import device; "
            "device.ensure_initialized(); "
            "jax.jit(lambda x: x + 1)(1).block_until_ready(); "
            "print(device.cache_dir_in_force())")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, check=True)
    assert r.stdout.strip() == "None"
    assert not os.listdir(tmp_path)


# -- nothing hides the device ------------------------------------------------

class _NoLimitDevice:
    def memory_stats(self):
        return {"bytes_in_use": 0}


def test_detect_budget_raises_on_tpu_without_limit(monkeypatch):
    import jax

    from spark_rapids_tpu.runtime.memory import DeviceMemoryManager
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "local_devices", lambda: [_NoLimitDevice()])
    with pytest.raises(LookupError, match="bytes_limit"):
        DeviceMemoryManager._detect_budget(0.85)


def test_detect_budget_takes_the_tightest_device(monkeypatch):
    import jax

    from spark_rapids_tpu.runtime.memory import DeviceMemoryManager

    class Dev:
        def __init__(self, limit):
            self.limit = limit

        def memory_stats(self):
            return {"bytes_limit": self.limit}

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [Dev(16 << 30), Dev(8 << 30)])
    assert DeviceMemoryManager._detect_budget(0.5) == 4 << 30
    monkeypatch.undo()
    assert DeviceMemoryManager._detect_budget(0.5) == 2 << 30  # CPU


def test_native_library_is_built_in_checkout_from_source_hash():
    import hashlib

    from spark_rapids_tpu import native
    lib = native.load_library("tudo")
    if lib is None:
        pytest.skip("no C++ toolchain here")
    src = os.path.join(REPO, "spark_rapids_tpu", "native", "tudo.cpp")
    with open(src, "rb") as f:
        digest = hashlib.sha1(f.read()).hexdigest()[:16]
    assert lib._name == os.path.join(
        REPO, "spark_rapids_tpu", "native", "_build",
        f"libtudo-{digest}.so")


def test_concat_colocates_batches_of_different_devices():
    """Partitions of an ICI exchange live on different devices; the
    operators that merge them (TopN, final merges) concat eagerly."""
    import jax
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.columnar.column import (
        device_to_host, host_to_device)
    from spark_rapids_tpu.exec.basic import concat_device_batches
    devs = jax.devices()
    assert len(devs) >= 4  # conftest forces 8 virtual CPU devices
    parts = [pa.table({"k": np.arange(i * 10, i * 10 + 5),
                       "v": np.arange(5) * 0.5}) for i in range(4)]
    batches = [jax.device_put(host_to_device(t), d)
               for t, d in zip(parts, devs)]
    for n in (2, 4):  # the sequential and the many-batch path
        out = concat_device_batches(batches[0].schema, batches[:n])
        assert out.sel.devices() == {devs[0]}
        got = device_to_host(out)
        assert got.column("k").to_pylist() == [
            k for t in parts[:n] for k in t.column("k").to_pylist()]
