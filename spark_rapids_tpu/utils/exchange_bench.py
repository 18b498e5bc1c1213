"""Exchange-bandwidth microbench of the multichip dry-run
(``__graft_entry__.py``).

Measures the SAME programs the engine's compiled exchange runs
(parallel/shuffle.py ``build_prepare_program`` / ``build_boundary_program``)
plus an in-memory floor for the host transport, per partition count —
so every record of it reports one consistent trajectory for the
0.05 GB/s → compiled-collective gap.

Three numbers per partition count:

* ``compiled`` — the boundary program alone (clip-gather + tiled
  all_to_all + receive mask): the marginal cost of a stage seam, what
  ``ici_all_to_all_virtual8`` tracks.  Reps are dispatched pipelined and
  synced once, the way a pump overlaps seams with compute.
* ``e2e`` — prepare + counts host round-trip + boundary: a full cold
  exchange of a never-before-partitioned batch.
* ``host`` — D2H, numpy stable partition sort, H2D.  Deliberately
  FLATTERING to the host path (no files, no serializer framing, no
  framing copies) so a compiled win over it is a lower bound.
"""

from __future__ import annotations

import time
from typing import Dict, Iterable, Optional, Sequence

import numpy as np


def _make_batch(n_rows: int, seed: int = 11):
    import pyarrow as pa

    from spark_rapids_tpu.columnar.column import host_to_device
    rng = np.random.default_rng(seed)
    table = pa.table({"k": rng.integers(0, 1 << 40, n_rows),
                      "v": rng.uniform(0, 1, n_rows)})
    return host_to_device(table)


def exchange_bench(n_rows: int = 1 << 22,
                   parts: Optional[Sequence[int]] = None,
                   reps: int = 10, e2e_reps: int = 3, host_reps: int = 3,
                   modes: Iterable[str] = ("compiled", "e2e", "host"),
                   ) -> Dict[str, Dict[str, float]]:
    """GB/s per partition count for the requested modes.

    ``n_rows`` must be a power of two (shard divisibility).  Returns
    ``{str(nparts): {"compiled": gbps, "e2e": gbps, "host": gbps,
    "bytes": payload}}`` — missing modes were not requested; a mode
    that cannot run on this platform records ``None``."""
    import jax

    from spark_rapids_tpu.columnar import dtypes as T
    from spark_rapids_tpu.ops.expressions import BoundReference
    from spark_rapids_tpu.parallel import shuffle as SH
    from spark_rapids_tpu.parallel.mesh import make_mesh, named_sharding
    from spark_rapids_tpu.runtime.device import ensure_initialized
    ensure_initialized()
    ndev = jax.device_count()
    if parts is None:
        parts = [ndev]
    batch = _make_batch(n_rows)
    nbytes = n_rows * 16  # k int64 + v float64 payload
    keys = [BoundReference(0, T.LongT)]
    out: Dict[str, Dict[str, float]] = {}
    for p in parts:
        if p > ndev:
            continue
        mesh = make_mesh(p)
        sharded = SH.shard_batch(mesh, batch)
        local_b = batch.capacity // p
        prep = SH.build_prepare_program(mesh, keys, p)
        idx, counts = prep(sharded)
        counts_np = np.asarray(counts).reshape(p, p)
        cap = SH.exchange_cap(counts_np.max(), local_b)
        shd = named_sharding(mesh)
        crecv = jax.device_put(
            np.ascontiguousarray(counts_np.T.astype(np.int32)), shd)
        # donate=False: the bench re-dispatches the same input buffers
        fn = SH.build_boundary_program(mesh, p, cap, donate=False)
        res: Dict[str, float] = {"bytes": nbytes}
        if "compiled" in modes:
            jax.block_until_ready(fn(sharded, idx, crecv))  # compile
            t0 = time.perf_counter()
            last = None
            for _ in range(reps):
                # pipelined: no sync between dispatches, but drop the
                # previous output so rep buffers recycle instead of
                # stacking up (keeping all alive costs ~60% bandwidth)
                last = fn(sharded, idx, crecv)
            jax.block_until_ready(last)
            per = (time.perf_counter() - t0) / reps
            res["compiled"] = nbytes / per / 1e9
            del last
        if "e2e" in modes:
            def one():
                idx2, c2 = prep(sharded)
                cnp = np.asarray(c2).reshape(p, p)
                cr = jax.device_put(
                    np.ascontiguousarray(cnp.T.astype(np.int32)), shd)
                jax.block_until_ready(fn(sharded, idx2, cr))
            one()  # warm
            t0 = time.perf_counter()
            for _ in range(e2e_reps):
                one()
            res["e2e"] = nbytes / ((time.perf_counter() - t0)
                                   / e2e_reps) / 1e9
        if "host" in modes:
            res["host"] = _host_floor_gbps(batch, keys, p, nbytes,
                                           host_reps)
        out[str(p)] = res
        del sharded, idx, counts, crecv
    return out


def _host_floor_gbps(batch, keys, nparts: int, nbytes: int,
                     reps: int) -> float:
    """The host transport's in-memory floor: D2H every leaf, numpy
    stable partition sort, H2D.  Pids are precomputed (free for the
    host path) — every cost left is one the real transport must pay."""
    import jax

    from spark_rapids_tpu.parallel import shuffle as SH
    # jit-exempt: bench-only pid precompute, not an engine hot path
    pid = np.asarray(jax.jit(SH.make_pid_fn(keys, nparts))(batch))
    k_dev = batch.columns[0].data
    v_dev = batch.columns[1].data

    def one():
        k = np.asarray(k_dev)
        v = np.asarray(v_dev)
        order = np.argsort(pid, kind="stable")
        jax.block_until_ready(jax.device_put((k[order], v[order])))

    one()  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        one()
    return nbytes / ((time.perf_counter() - t0) / reps) / 1e9
