"""Operator-kernel executable cache.

THE TPU-idiom mechanism (SURVEY §7): each physical operator's device work
is one jitted function, cached by the operator's *structural fingerprint*
(expression tree, literals, dtypes, options); jax's own jit cache then
keys on input shapes, so each (op, schema, bucket) pair compiles exactly
once and stays hot across queries — the analog of cuDF's precompiled
kernels, and essential on TPU where eager dispatch means one XLA
compilation per arithmetic op.

Three layers, innermost first: jax's jit cache (per shape bucket), this
module's fingerprint cache (per op structure), and — when
off the CPU backend — jax's on-disk compilation cache (survives
process restarts; ``runtime/device.py::compile_cache_dir`` places
it).  The shape plane (runtime/shapes.py)
bounds the bucket axis so all three stay small.
"""

from __future__ import annotations

import dataclasses
import re
import threading
import time
from typing import Callable, Dict, Optional, Tuple

import jax

from spark_rapids_tpu.runtime import resilience as R
from spark_rapids_tpu.runtime import telemetry as TM
from spark_rapids_tpu.runtime import trace

_CACHE: Dict[tuple, Callable] = {}
# partitions pump on a thread pool: without a lock, racing threads each
# build their own jit wrapper for the same key and XLA compiles twice
_CACHE_LOCK = threading.Lock()

_TM_HITS = TM.REGISTRY.counter(
    "tpuq_kernel_cache_hits_total",
    "cached_kernel lookups served by the fingerprint cache")
_TM_MISSES = TM.REGISTRY.counter(
    "tpuq_kernel_cache_misses_total",
    "cached_kernel lookups that built a new jit wrapper")
_TM_COMPILES = TM.REGISTRY.counter(
    "tpuq_kernel_compile_total", "XLA compilations observed")
_TM_COMPILE_S = TM.REGISTRY.counter(
    "tpuq_kernel_compile_seconds_total",
    "seconds spent in dispatches that triggered an XLA compile")
_TM_LAUNCHES = TM.REGISTRY.counter(
    "tpuq_program_launches_total",
    "calls of a cached kernel: every program the kernel cache "
    "launched, tracer or not")
TM.REGISTRY.gauge(
    "tpuq_kernel_cache_size", "live cached kernel wrappers",
    fn=lambda: len(_CACHE))


def fingerprint(v) -> object:
    """Structural, hashable key for expression/aggregate trees."""
    from spark_rapids_tpu.columnar import dtypes as T

    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        if isinstance(v, T.DataType):
            return v.simple_name
        return (type(v).__name__,) + tuple(
            fingerprint(getattr(v, f.name)) for f in dataclasses.fields(v))
    if isinstance(v, (list, tuple)):
        return tuple(fingerprint(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, fingerprint(x)) for k, x in v.items()))
    return repr(v)


def _jit_once(fn: Callable, label: str) -> Callable:
    """jit ``fn`` under the name ``tpuq_<label>``, its body under
    ``jax.named_scope(label)``, unless the builder already jitted it:
    the host's ``PjitFunction(tpuq_<label>)`` event, the device's
    ``jit_tpuq_<label>`` module and each op's metadata then say which
    kernel they are.  The name is set once, here, so it costs a call
    nothing and a warm window compiles nothing.

    SPMD exchange programs come out of their builders pre-jitted with
    ``donate_argnums`` — re-wrapping them would trace THROUGH the inner
    pjit and silently drop the donation annotation (the outer jit's
    donation set, empty, is the one that counts).  ``_cache_size`` is
    the jit-wrapper attribute the compile detector below already keys
    on, so its presence is the reliable already-jitted signal."""
    if hasattr(fn, "_cache_size"):
        return fn

    def named(*args, **kw):
        with jax.named_scope(label):
            return fn(*args, **kw)

    named.__name__ = named.__qualname__ = f"tpuq_{label}"
    return jax.jit(named)


def _build_wrapper(key: tuple, builder: Callable[[], Callable]):
    """jit the built kernel through the ``compile`` failure domain.

    The chokepoint fires at jit-wrapper construction (the cache-miss
    boundary every XLA compile passes).  Degradation returns the raw
    un-jitted builder output — eager per-op dispatch instead of one
    compiled executable."""
    label = _op_label(key)
    if not R.active():
        return _jit_once(builder(), label)

    def attempt():
        R.INJECTOR.on("compile")
        return _jit_once(builder(), label)

    def degrade():
        return builder()

    return R.run_guarded("compile", attempt, op=label, degrade=degrade)


def _op_label(key: tuple) -> str:
    """The key's head (``agg_reduce``, ``join_mat``, ``concat_norm`` …)
    as an identifier: the kernel's name in spans, jit names and scopes."""
    head = key[0] if key else "kernel"
    return re.sub(r"\W", "_", head if isinstance(head, str) else repr(head))


def cached_kernel(key: tuple, builder: Callable[[], Callable]) -> Callable:
    """Return the jitted kernel for key, building+jitting it on first use.

    jax.jit itself is lazy (tracing happens at first call), so holding the
    lock across build+insert is cheap.  Every call passes the fault
    injector's execute chokepoint [REF: faultinj analog, SURVEY N15] —
    an attribute check when disarmed, a policy-guarded call when armed
    (or when this op's breaker is already open).  Exhausted retries
    degrade to re-running the op's builder eagerly, outside the failing
    compiled executable."""
    with _CACHE_LOCK:
        fn = _CACHE.get(key)
        if fn is not None:
            _TM_HITS.inc()
            return fn
        _TM_MISSES.inc()
        jfn = _build_wrapper(key, builder)
        label = _op_label(key)
        span_op = "Kernel." + label

        def _call(args, kw, __jfn=jfn, __builder=builder):
            if not R.active():
                return __jfn(*args, **kw)

            def attempt():
                R.INJECTOR.on("execute")
                return __jfn(*args, **kw)

            def degrade():
                return __builder()(*args, **kw)

            return R.run_guarded("execute", attempt, op=label,
                                 degrade=degrade)

        def fn(*args, __jfn=jfn, **kw):
            _TM_LAUNCHES.inc()
            tr = trace.current()
            # jax.jit compiles lazily at first call per shape bucket;
            # the cache-size delta distinguishes an XLA compile from a
            # hot dispatch — compiles get their own span stage and the
            # registry's compile count/time
            before = (__jfn._cache_size()
                      if hasattr(__jfn, "_cache_size") else None)
            if tr is None and before is None:
                return _call(args, kw)
            t0 = time.perf_counter()
            sp = (tr.begin(span_op, "kernelLaunch")
                  if tr is not None else None)
            try:
                return _call(args, kw)
            finally:
                if (before is not None
                        and __jfn._cache_size() > before):
                    _TM_COMPILES.inc()
                    _TM_COMPILE_S.inc(time.perf_counter() - t0)
                    if sp is not None:
                        sp.stage = "compile"
                if sp is not None:
                    tr.end(sp)

        _CACHE[key] = fn
        return fn


def cache_stats() -> Tuple[int,]:
    return (len(_CACHE),)


def compile_snapshot() -> Tuple[int, float]:
    """(compile count, compile seconds) observed so far — the
    before/after pair ``session.warmup``, ``chip_smoke.py`` and
    ``benchmark/counters.py`` diff to attribute compiles to a phase
    (warmup, cold run, warm run, a cell's window)."""
    return (int(_TM_COMPILES.value), float(_TM_COMPILE_S.value))


# ---------------------------------------------------------------------------
# Persistent compilation cache (spark.rapids.tpu.kernel.cacheDir)
# ---------------------------------------------------------------------------
#
# The in-process layers above make each (op, schema, bucket) compile once
# per PROCESS; this layer makes it compile once per MACHINE: a fresh
# QueryServer process whose cache directory was warmed by a previous
# run (or by ``session.warmup``) loads executables from disk instead of
# invoking XLA on the hot path.  The manifest below guards only a
# directory the user named with kernel.cacheDir.

MANIFEST_NAME = "tpuq_cache_manifest.json"


def _cache_versions() -> Dict[str, str]:
    """The compatibility tuple a cache directory is valid for."""
    import jaxlib

    from spark_rapids_tpu import __version__ as engine_version
    return {"format": "1", "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "engine": engine_version}


def _sync_manifest(cache_dir: str) -> bool:
    """Validate ``cache_dir`` against the current versions.

    Returns True when existing entries were kept (manifest matched).
    On mismatch — a different jax/jaxlib/engine wrote them, and XLA's
    serialized executables make no cross-version promises — every entry
    is dropped and the manifest is rewritten for this build."""
    import json
    import os
    import shutil
    path = os.path.join(cache_dir, MANIFEST_NAME)
    want = _cache_versions()
    try:
        with open(path) as f:
            have = json.load(f)
    except (OSError, ValueError):
        have = None
    if have == want:
        return True
    for name in os.listdir(cache_dir):
        if name == MANIFEST_NAME:
            continue
        p = os.path.join(cache_dir, name)
        try:
            if os.path.isdir(p):
                shutil.rmtree(p)
            else:
                os.unlink(p)
        except OSError:
            pass  # a torn delete only costs one stale entry re-check
    with open(path, "w") as f:
        json.dump(want, f)
    return False


def configure_persistent_cache(conf) -> Optional[str]:
    """Route ``kernel.cacheDir`` through runtime/device.py's one choice
    of compile-cache directory.

    Called at session init (after the backend is resolved).  An empty
    cacheDir leaves what ``ensure_initialized`` put in force;
    ``JAX_COMPILATION_CACHE_DIR`` outranks the conf; on the XLA:CPU
    backend the cache is off whatever is set (see
    ``device.configure_compile_cache``).  Returns the directory in
    force, or None when the cache is off."""
    from spark_rapids_tpu import conf as C
    from spark_rapids_tpu.runtime import device
    device.ensure_initialized()
    cache_dir = str(conf.get(C.KERNEL_CACHE_DIR)).strip()
    if not cache_dir:
        return device.cache_dir_in_force()
    return device.configure_compile_cache(cache_dir)


def clear() -> None:
    """Drop every cached kernel wrapper AND jax's compiled executables.

    Needed by long single-process runs on the CPU platform: XLA:CPU
    JIT-compiled executables accumulate in code memory, and past a few
    hundred live programs LLVM's emitter can crash the process during a
    NEW compilation (observed as a SIGSEGV inside
    ``backend_compile_and_load`` late in the test suite).  Clearing
    between test modules bounds live executables; kernels lazily
    recompile on next use."""
    with _CACHE_LOCK:
        _CACHE.clear()
    jax.clear_caches()
