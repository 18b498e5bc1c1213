"""chip_smoke.py on the CPU, and the pieces it leans on that must not
hide the device: compile-cache placement, HBM budget detection, the
native library build, the multi-device concat, bench.py's child
accounting."""

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


def test_rehearse_runs_every_phase_on_cpu():
    r, lines = _run_smoke("--rehearse")
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
    assert len(compares) == 6 and all(c["equal"] for c in compares)
    assert lines[0]["compile_cache_dir"] is None  # off on the CPU
    # submitted together, each served query closed a ledger of its own
    served = [ln for ln in lines if ln.get("phase") == "served"
              and "query" in ln]
    assert [ln["query"] for ln in served] == ["q6", "q1", "q12"]
    assert all(ln["books"] == 1 and "queue_wait" in ln["buckets"]
               for ln in served)


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
    assert cmp_["against"] == "one_device" and cmp_["equal"]


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


# -- bench.py: one process per chip, and failures are loud -------------------

def test_bench_child_failures_and_devices_are_recorded(monkeypatch):
    sys.path.insert(0, REPO)
    import bench
    monkeypatch.setattr(bench, "FAILURES", [])
    monkeypatch.setattr(bench, "CHILD_DEVICES", {})
    r = bench.run_child("bad", ["--sf1-query", "no_such_query"], 120)
    assert r.returncode != 0
    assert bench.FAILURES == [f"bad: rc={r.returncode}"]
    assert bench.CHILD_DEVICES["bad"]["platform"] == "cpu"


def test_bench_parent_imports_no_jax_and_roofline_is_keyed():
    code = ("import sys, bench; assert 'jax' not in sys.modules; "
            "print(sorted(bench.HBM_GB_PER_S))")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, check=True)
    assert r.stdout.strip() == "['TPU v5 lite']"
