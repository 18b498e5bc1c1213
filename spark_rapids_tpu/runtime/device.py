"""Device runtime bootstrap — the ``GpuDeviceManager`` analog.

[REF: sql-plugin/../GpuDeviceManager.scala :: initializeGpuAndMemory]
Responsible for one-time engine initialization: exact-numerics mode (x64),
device discovery, and (see ``runtime/memory.py``) the HBM budget arbiter.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional, Tuple

_LOG = logging.getLogger(__name__)

_init_lock = threading.Lock()
_initialized = False


def ensure_initialized() -> None:
    """One-time engine init.  Called by every engine entry point (session
    creation, host<->device transfer), NOT at import, so importing the
    package does not change process-global JAX semantics for host programs
    that never run a query.

    SQL engines need exact 64-bit integer/floating semantics (Spark
    LongType/DoubleType, Decimal backed by int64), so x64 is enabled for the
    process once the engine is actually used.  TPU emulates int64;
    correctness over raw speed — hot kernels opt into 32-bit where safe.
    """
    global _initialized
    if _initialized:
        return
    with _init_lock:
        if _initialized:
            return
        import jax

        jax.config.update("jax_enable_x64", True)
        configure_compile_cache()
        _initialized = True


# ---------------------------------------------------------------------------
# Persistent XLA executable cache: operator kernels (sort-heavy, minutes
# to compile on a TPU) compile once per machine, not per process.
# ---------------------------------------------------------------------------

# fixed default inside the checkout: the path is part of what makes a
# second run find the first one's entries, so never $HOME, a temp name,
# a pid or a time
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
_cache_dir_in_force: Optional[str] = None


def compile_cache_dir(conf_dir: str = "") -> Tuple[str, str]:
    """THE choice of compile-cache directory → ``(path, origin)``.

    1. ``JAX_COMPILATION_CACHE_DIR`` (origin ``env``): that directory as
       it is — no sub-directory, no manifest, nothing deleted — and
       ``conf_dir`` is ignored;
    2. ``spark.rapids.tpu.kernel.cacheDir`` (origin ``conf``): the
       per-machine sub-directory of it that carries the version
       manifest (runtime/kernel_cache.py);
    3. ``<checkout>/.jax_cache`` (origin ``checkout``).

    jax's own cache key already covers the jax version and the chip.
    Pure: touches nothing on disk."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env, "env"
    if conf_dir:
        return os.path.join(os.path.expanduser(conf_dir),
                            _machine_fingerprint()), "conf"
    return _CHECKOUT_CACHE, "checkout"


def configure_compile_cache(conf_dir: str = "") -> Optional[str]:
    """Put jax's persistent cache where ``compile_cache_dir`` says;
    returns the directory in force (None: cache off).

    OFF on the CPU backend: XLA:CPU AOT executables carry target
    pseudo-features (+prefer-no-gather …) the loader's host check
    rejects, and reading such an entry SEGFAULTS the process (observed
    under the test suite's forced CPU platform — same machine, fresh
    cache).  The resolved backend decides, not the config string:
    jax_platforms is None when jax auto-selects."""
    global _cache_dir_in_force
    import jax
    if jax.default_backend() == "cpu":
        if jax.config.jax_compilation_cache_dir:
            # placed from outside (env): jax would use it here as well
            jax.config.update("jax_enable_compilation_cache", False)
        _cache_dir_in_force = None
        return None
    path, origin = compile_cache_dir(conf_dir)
    if origin == "env":
        # jax reads the variable itself: no directory is set in code
        if conf_dir:
            _LOG.warning(
                "spark.rapids.tpu.kernel.cacheDir=%s ignored: "
                "JAX_COMPILATION_CACHE_DIR=%s is in force", conf_dir, path)
    else:
        os.makedirs(path, exist_ok=True)
        if origin == "conf":
            from spark_rapids_tpu.runtime.kernel_cache import _sync_manifest
            _sync_manifest(path)
        jax.config.update("jax_compilation_cache_dir", path)
    # persist EVERY executable, not only slow ones: the warm-restart
    # contract is zero hot-path compiles, and a 50 ms compile skipped
    # from disk is still a compile the storm detector would count
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _cache_dir_in_force = path
    return path


def cache_dir_in_force() -> Optional[str]:
    """The directory the persistent compile cache writes to, if on."""
    return _cache_dir_in_force


def _machine_fingerprint() -> str:
    """Short hash of the host's CPU feature flags (the sub-directory of
    a user-named ``kernel.cacheDir``, which hosts may share)."""
    import hashlib
    import platform
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    return hashlib.sha1(
                        line.encode()).hexdigest()[:12]
    except OSError:
        pass
    return platform.machine()
