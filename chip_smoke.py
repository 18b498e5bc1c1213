#!/usr/bin/env python3
"""Quickest proof that tpuq still starts on the attached chip.

One process drives the main path — Arrow table -> ``TpuSession`` plan ->
device kernels -> ``toArrow()``, and the same through
``QueryServer.submit`` — at TPC-H SF1 with the default conf, and holds
every result against a ``spark.rapids.sql.enabled=False`` session on
the same tables.  One JSON object per line; the last line is the
verdict::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The run FAILS (non-zero exit, ``"ok": false`` last) when the platform
is not ``tpu``, a plan node of these queries fell back to the CPU, an
op was host-degraded or a breaker tripped, no dispatch ran on the
``pallas`` rung, a warm run compiled anything, or a comparison
differs.  No phase is wrapped so that the run could carry on.

The tables, the queries, the compile counts and the comparison are the
benchmark's own (``benchmark/tpch_gen.py``, ``queries/q6|q1|q12.py`` with
the first binding each draws from ``--seed``, ``counters.py``,
``compare.py``): bring-up is proved on the data and plans the cells of
``BENCHMARK.json`` measure.

``--chips 4`` runs ONLY the multi-chip path (the join query under
``spark.rapids.shuffle.mode=ICI`` across four devices) and the same
query on one device as what it is compared with.

``--rehearse`` (never the default) runs the same phases at ``--sf 0.01``
on whatever backend is there, for tests/test_chip_smoke.py; without it
the script refuses any platform but ``tpu``.
"""

import argparse
import json
import math
import os
import sys
import time
import traceback

# benchmark/ is no package: its modules are found as run.py finds them
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark"))
import compare  # noqa: E402
import counters  # noqa: E402
import tpch_gen  # noqa: E402
from run import load_module  # noqa: E402

RTOL = 1e-9  # README "Numerics": doubles are not bit-exact on the chip
JOIN_QUERY = "q12"  # the join server.throughput runs
QUERIES = ("q6", "q1", JOIN_QUERY)
TENANTS = ("tenant_a", "tenant_b")
# the conf a user on a TPU gets: `auto` picks every backend
DEFAULT_CONF = {"spark.rapids.sql.enabled": True}


class SmokeFailure(Exception):
    """A check of this script did not hold."""


def emit(phase: str, **kw) -> None:
    print(json.dumps({"phase": phase, **kw}), flush=True)


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def memory_stat(devices, key: str) -> list:
    """One allocator statistic per device, or None where the backend
    keeps no stats (the CPU backend of a rehearsal)."""
    return [(d.memory_stats() or {}).get(key) for d in devices]


def live_array_bytes(devices) -> list:
    """Bytes of live jax arrays per device (what jax itself tracks)."""
    import jax
    held = {d.id: 0 for d in devices}
    for a in jax.live_arrays():
        for s in a.addressable_shards:
            if s.device.id in held:
                held[s.device.id] += s.data.nbytes
    return [held[d.id] for d in devices]


def bind(name: str, seed: int):
    """``build(session, tables)`` of ``benchmark/queries/<name>.py`` with
    the first binding it draws from the seed."""
    import numpy as np
    q = load_module("queries", name)
    b = q.draw_bindings(np.random.default_rng(seed), 1)[0]
    emit("binding", query=name, **b)
    return lambda session, tables: q.build(session, tables, b)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(conf: dict, devices, info: dict) -> None:
    import jax
    import numpy as np

    from spark_rapids_tpu.runtime import device, memory
    from spark_rapids_tpu.shuffle import serializer
    d0 = devices[0]
    # what a float64 looks like after a round trip through this device
    back = float(np.asarray(jax.device_put(np.float64(3.3), d0)))
    neg0 = float(np.asarray(jax.device_put(np.float64(-0.0), d0)))
    emit("device", **info, jax=jax.__version__, conf=conf,
         compile_cache_dir=device.cache_dir_in_force(),
         hbm_budget_bytes=memory.get_manager().budget,
         native_serializer_loaded=serializer.native_enabled(),
         f64={"put_3.3_back_hex": back.hex(),
              "put_3.3_bit_exact": back == 3.3,
              "neg_zero_keeps_sign": math.copysign(1.0, neg0) < 0})


def phase_data(sf: float, seed: int) -> dict:
    t0 = time.monotonic()
    tables = tpch_gen.gen_tables(sf, seed)
    emit("data", sf=sf, seed=seed, gen_s=time.monotonic() - t0,
         rows={k: t.num_rows for k, t in tables.items()},
         arrow_bytes=sum(t.nbytes for t in tables.values()))
    return tables


def run_direct(session, name: str, build, tables, xla, phase="direct"):
    """Cold then warm ``toArrow()`` of one query; returns the cold
    result and the DataFrame (its plan stays alive with it)."""
    df = build(session, tables)
    c0 = counters.snapshot(xla)
    t0 = time.monotonic()
    cold = df.toArrow()
    cold_s = time.monotonic() - t0
    c1 = counters.snapshot(xla)
    t0 = time.monotonic()
    warm = df.toArrow()
    warm_s = time.monotonic() - t0
    c2 = counters.snapshot(xla)
    dc, dw = counters.delta(c1, c0), counters.delta(c2, c1)
    fb = df.fallback_summary()
    both = counters.delta(c2, c0)
    rec = {
        "query": name, "cold_s": cold_s, "warm_s": warm_s,
        "kernel_compiles_cold": dc["kernel_compiles"],
        "kernel_compile_s_cold": dc["kernel_compile_s"],
        "kernel_compiles_warm": dw["kernel_compiles"],
        "xla_compile_requests": dc["xla_compile_requests"],
        "persistent_cache_hits": dc["persistent_cache_hits"],
        "xla_compiles_cold": dc["xla_compiles"],
        "xla_compile_s_cold": dc["xla_compile_s"],
        "xla_compiles_warm": dw["xla_compiles"],
        "dispatch_by_backend": both["dispatch_by_backend"],
        "ladder_descents": both["ladder_descents"],
        "host_degraded_ops": both["host_degraded_ops"],
        "breaker_trips": both["breaker_trips"],
        "device_ops": fb["device_ops"],
        "fallback_ops": fb["fallback_ops"],
        "fallback_reasons": fb["fallback_reasons"],
        "warm_equals_cold": warm.equals(cold),
    }
    emit(phase, **rec)
    require(rec["fallback_ops"] == 0,
            f"{name}: plan nodes left on the CPU: {fb['fallback_reasons']}")
    require(rec["host_degraded_ops"] == 0 and rec["breaker_trips"] == 0,
            f"{name}: host-degraded ops / breaker trips: {rec}")
    require(rec["kernel_compiles_warm"] == 0
            and rec["xla_compiles_warm"] == 0,
            f"{name}: the warm run compiled")
    require(rec["warm_equals_cold"], f"{name}: warm result != cold result")
    return cold, df


def phase_served(session, builders: dict, tables, xla) -> dict:
    """The same queries through the serving front door, submitted
    together under two tenants."""
    from spark_rapids_tpu.runtime import attribution, memory
    from spark_rapids_tpu.sql.server import QueryServer
    server = QueryServer(session)
    c0 = counters.snapshot(xla)
    t0 = time.monotonic()
    handles = [
        (name, server.submit(
            lambda b=build: b(session, tables),
            tenant=TENANTS[i % len(TENANTS)]))
        for i, (name, build) in enumerate(builders.items())]
    results = {}
    for name, h in handles:
        results[name] = server.result(h, timeout_s=900)
        books = [b for b in attribution.recent()
                 if b["query_id"] == h.query_id]
        emit("served", query=name, tenant=h.tenant, state=h.state,
             queue_wait_s=h.queue_wait_s, wall_s=h.wall_s,
             books=len(books),
             buckets=({k: v for k, v in books[0]["buckets"].items() if v}
                      if books else None))
        require(h.state == "OK", f"served {name} ended {h.state}")
        # the queries are submitted together and run beside each other:
        # each has to close a ledger of its own (runtime/inflight.py)
        require(len(books) == 1,
                f"served {name} (query {h.query_id}) published "
                f"{len(books)} ledgers of its own, not 1")
    wall = time.monotonic() - t0
    stats = server.stats()
    server.shutdown()
    d = counters.delta(counters.snapshot(xla), c0)
    leaks = memory.get_manager().report_leaks()
    emit("served", submits=len(handles), wall_s=wall, leaks=leaks,
         tenants={t: int(s.get("completed", 0)) for t, s in stats.items()},
         kernel_compiles=d["kernel_compiles"],
         xla_compiles=d["xla_compiles"],
         dispatch_by_backend=d["dispatch_by_backend"],
         host_degraded_ops=d["host_degraded_ops"],
         breaker_trips=d["breaker_trips"])
    require(leaks == 0, f"{leaks} spillable batches leaked after shutdown")
    require(d["host_degraded_ops"] == 0 and d["breaker_trips"] == 0,
            f"served: host-degraded ops / breaker trips: {d}")
    return results


def phase_compare(builders: dict, tables, direct: dict,
                  served: dict) -> float:
    """Outside any timed window: every result against the CPU twin."""
    from spark_rapids_tpu.sql.session import TpuSession
    twin = TpuSession({"spark.rapids.sql.enabled": False})
    worst = 0.0
    for name, build in builders.items():
        t0 = time.monotonic()
        want = build(twin, tables).toArrow()
        ref_s = time.monotonic() - t0
        for kind, got in (("direct", direct[name]), ("served", served[name])):
            c = compare.compare_tables(got, want)
            emit("compare", query=name, against="cpu_twin", result_of=kind,
                 reference_s=ref_s, rtol=RTOL, rows=want.num_rows, **c)
            # keys, counts, row order and null positions exact, doubles
            # at RTOL
            require(c["exact_mismatches"] == 0 and c["max_rel_err"] <= RTOL,
                    f"{name} ({kind}) differs from the CPU twin: {c}")
            worst = max(worst, c["max_rel_err"])
    return worst


def run_one_chip(args, session, devices, tables, xla, t_start) -> None:
    builders = {n: bind(n, args.seed) for n in QUERIES}
    direct = {n: run_direct(session, n, b, tables, xla)[0]
              for n, b in builders.items()}
    served = phase_served(session, builders, tables, xla)
    total = counters.snapshot(xla)
    pallas = int(total["dispatch_by_backend"].get("pallas", 0))
    emit("kernels", rungs_used={k: int(v) for k, v in
                                total["dispatch_by_backend"].items()},
         pallas_dispatches=pallas)
    if not args.rehearse:
        require(pallas > 0, "no dispatch ran on the pallas rung")
    worst = phase_compare(builders, tables, direct, served)
    emit("summary", wall_s=time.monotonic() - t_start,
         kernel_compiles=total["kernel_compiles"],
         kernel_compile_s=total["kernel_compile_s"],
         xla_compile_requests=total["xla_compile_requests"],
         persistent_cache_hits=total["persistent_cache_hits"],
         xla_compiles=total["xla_compiles"],
         xla_compile_s=total["xla_compile_s"],
         max_rel_err=worst,
         peak_bytes_in_use=memory_stat(devices, "peak_bytes_in_use"))


# ---------------------------------------------------------------------------
# --chips N: the ICI path and its one-device comparison, nothing else
# ---------------------------------------------------------------------------

def ici_exchanges(plan) -> tuple:
    """(planned, executed) ICI exchange nodes of a plan (hash and
    range), and the class of every exchange node in it."""
    from spark_rapids_tpu.exec.distributed import TpuIciShuffleExchangeExec
    planned = executed = 0
    kinds = []
    stack = [plan]
    while stack:
        node = stack.pop()
        if "Exchange" in type(node).__name__:
            kinds.append(type(node).__name__)
        if isinstance(node, TpuIciShuffleExchangeExec):
            planned += 1
            executed += node._result is not None
        stack.extend(node.children)
    return planned, executed, sorted(kinds)


def run_multi_chip(args, devices, info, tables, xla, t_start) -> None:
    from spark_rapids_tpu.runtime import telemetry as TM
    from spark_rapids_tpu.sql.session import TpuSession
    n = len(devices)
    ici_conf = {"spark.rapids.sql.enabled": True,
                "spark.rapids.shuffle.mode": "ICI",
                "spark.default.parallelism": n}
    ici = TpuSession(ici_conf)
    phase_device(ici_conf, devices, info)
    ici_bytes = TM.REGISTRY.counter("tpuq_ici_exchange_bytes_total")
    b0 = ici_bytes.value
    name, build = JOIN_QUERY, bind(JOIN_QUERY, args.seed)
    got, df = run_direct(ici, name, build, tables, xla, phase="ici")
    planned, executed, kinds = ici_exchanges(df._last_plan)
    live = live_array_bytes(devices)
    emit("ici_placement", when="after_query_plan_alive",
         bytes_in_use=memory_stat(devices, "bytes_in_use"),
         peak_bytes_in_use=memory_stat(devices, "peak_bytes_in_use"),
         live_array_bytes=live, ici_exchanges_planned=planned,
         ici_exchanges_executed=executed, exchange_nodes=kinds,
         ici_exchange_bytes=int(ici_bytes.value - b0))
    # with the plan (and so each exchange's received shards) alive
    require(sum(1 for b in live if b) >= n,
            f"fewer than {n} devices hold data: {live}")
    require(planned > 0 and executed > 0 and ici_bytes.value > b0,
            f"no ICI exchange ran (planned {planned}, executed {executed})")
    del df
    emit("ici_placement", when="after_query_plan_dropped",
         bytes_in_use=memory_stat(devices, "bytes_in_use"),
         live_array_bytes=live_array_bytes(devices))
    one = TpuSession(DEFAULT_CONF)
    want, _ = run_direct(one, name, build, tables, xla, phase="one_device")
    c = compare.compare_tables(got, want)
    emit("compare", query=name, against="one_device", result_of="ici",
         rtol=RTOL, rows=want.num_rows, **c)
    require(c["exact_mismatches"] == 0 and c["max_rel_err"] <= RTOL,
            f"ICI result differs from one device: {c}")
    total = counters.snapshot(xla)
    emit("summary", wall_s=time.monotonic() - t_start,
         kernel_compile_s=total["kernel_compile_s"],
         xla_compile_requests=total["xla_compile_requests"],
         persistent_cache_hits=total["persistent_cache_hits"],
         xla_compiles=total["xla_compiles"],
         max_rel_err=c["max_rel_err"],
         peak_bytes_in_use=memory_stat(devices, "peak_bytes_in_use"))


# ---------------------------------------------------------------------------

def run(args) -> dict:
    t_start = time.monotonic()
    if args.rehearse and args.chips > 1:
        # virtual CPU devices for the multi-chip rehearsal; read when
        # the CPU client is created, so before jax is touched
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearse:
        require(info["platform"] == "tpu",
                f"platform is {info['platform']!r}, not 'tpu' "
                "(--rehearse runs a small CPU rehearsal)")
    require(len(devices) >= args.chips,
            f"--chips {args.chips} but jax sees {len(devices)} device(s)")
    devices = devices[:args.chips] if args.chips > 1 else devices
    from spark_rapids_tpu.sql.session import TpuSession
    xla = counters.XlaCounts()
    sf = args.sf if args.sf is not None else (0.01 if args.rehearse else 1.0)
    if not args.rehearse:
        require(sf >= 1.0, "the chip run is sized at SF1; --sf below 1 "
                           "needs --rehearse")
    if args.chips > 1:
        tables = phase_data(sf, args.seed)
        run_multi_chip(args, devices, info, tables, xla, t_start)
    else:
        session = TpuSession(DEFAULT_CONF)
        phase_device(DEFAULT_CONF, devices, info)
        tables = phase_data(sf, args.seed)
        run_one_chip(args, session, devices, tables, xla, t_start)
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--sf", type=float, default=None,
                    help="TPC-H scale factor (default 1.0; 0.01 with "
                         "--rehearse)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the ICI path and its one-device twin")
    ap.add_argument("--rehearse", action="store_true",
                    help="small run on whatever backend is there (tests)")
    args = ap.parse_args(argv)
    # the ONE catch of this script: it turns any failure into the
    # contract's last line and a non-zero exit, never into carrying on
    try:
        info = run(args)
    except BaseException as e:
        traceback.print_exc()
        sys.stderr.flush()
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
