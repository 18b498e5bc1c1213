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

RTOL = 1e-9  # README "Numerics": doubles are not bit-exact on the chip
# The join query of the default run.  q3 (two joins + aggregate +
# top-N) compiles 183 XLA programs on a cold v5e, which leaves the
# 1200 s limit of the default run too little margin; q12 is the
# one-join substitute.  ``--join-query q3`` still runs q3.
JOIN_QUERY = "q12"
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


# ---------------------------------------------------------------------------
# counters: everything a phase reports is a delta of these
# ---------------------------------------------------------------------------

class XlaCounts:
    """jax's own compile events: requests that consulted the persistent
    cache, hits read back from it, and compiles the backend really ran."""

    def __init__(self):
        import jax.monitoring as mon
        self.requests = self.hits = self.compiles = 0
        self.compile_s = 0.0
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _on_duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs


def counters(xla: XlaCounts) -> dict:
    from spark_rapids_tpu.runtime import resilience
    from spark_rapids_tpu.runtime import telemetry as TM
    from spark_rapids_tpu.runtime.kernel_cache import compile_snapshot
    kc, ks = compile_snapshot()
    res = resilience.counters_snapshot()
    return {
        "kernel_compiles": kc, "kernel_compile_s": ks,
        "xla_compile_requests": xla.requests,
        "persistent_cache_hits": xla.hits,
        "xla_compiles": xla.compiles, "xla_compile_s": xla.compile_s,
        "dispatch_by_backend": TM.REGISTRY.labeled_counter(
            "tpuq_kernel_dispatch_total", label="backend").child_values(),
        "ladder_descents": TM.REGISTRY.labeled_counter(
            "tpuq_kernel_fallback_total", label="kernel").child_values(),
        "host_degraded_ops": res["host_degraded_ops"],
        "breaker_trips": res["breaker_trips"],
    }


def delta(after: dict, before: dict) -> dict:
    out = {}
    for k, v in after.items():
        if isinstance(v, dict):
            d = {lk: lv - before[k].get(lk, 0) for lk, lv in v.items()}
            out[k] = {lk: int(lv) for lk, lv in d.items() if lv}
        else:
            out[k] = v - before[k]
    return out


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


# ---------------------------------------------------------------------------
# comparison: keys, counts and row order exact; doubles at RTOL, ±0.0 equal
# ---------------------------------------------------------------------------

def compare_tables(got, want) -> dict:
    import numpy as np
    import pyarrow as pa
    diffs = []
    max_rel = 0.0
    if got.column_names != want.column_names:
        diffs.append(f"columns {got.column_names} != {want.column_names}")
    elif got.num_rows != want.num_rows:
        diffs.append(f"rows {got.num_rows} != {want.num_rows}")
    else:
        for name in want.column_names:
            g, w = got.column(name), want.column(name)
            if not pa.types.is_floating(w.type):
                if not g.equals(w):
                    diffs.append(f"{name}: exact column differs")
                continue
            if g.is_valid().to_pylist() != w.is_valid().to_pylist():
                diffs.append(f"{name}: null positions differ")
                continue
            gv = g.to_numpy(zero_copy_only=False).astype(np.float64)
            wv = w.to_numpy(zero_copy_only=False).astype(np.float64)
            live = ~np.isnan(wv)
            if not np.array_equal(np.isnan(gv), ~live):
                diffs.append(f"{name}: NaN positions differ")
                continue
            # -0.0 == 0.0 under plain subtraction: ±0.0 compare equal;
            # against an exact zero the error counts absolutely
            mag = np.abs(wv[live])
            rel = np.abs(gv[live] - wv[live]) / np.where(mag > 0, mag, 1.0)
            if rel.size:
                max_rel = max(max_rel, float(rel.max()))
                if float(rel.max()) > RTOL:
                    diffs.append(f"{name}: max rel err {rel.max()!r}")
    return {"equal": not diffs, "rows": want.num_rows,
            "max_rel_err": max_rel, "diffs": diffs}


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
    import bench
    t0 = time.monotonic()
    tables = bench.gen_tpch(sf, seed)
    emit("data", sf=sf, seed=seed, gen_s=time.monotonic() - t0,
         rows={k: t.num_rows for k, t in tables.items()},
         arrow_bytes=sum(t.nbytes for t in tables.values()))
    return tables


def run_direct(session, name: str, tables, xla: XlaCounts, phase="direct"):
    """Cold then warm ``toArrow()`` of one query; returns the cold
    result and the DataFrame (its plan stays alive with it)."""
    import bench
    df = bench.TPCH_BUILDERS[name](session, tables)
    c0 = counters(xla)
    t0 = time.monotonic()
    cold = df.toArrow()
    cold_s = time.monotonic() - t0
    c1 = counters(xla)
    t0 = time.monotonic()
    warm = df.toArrow()
    warm_s = time.monotonic() - t0
    c2 = counters(xla)
    dc, dw = delta(c1, c0), delta(c2, c1)
    fb = df.fallback_summary()
    both = delta(c2, c0)
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


def phase_served(session, names, tables, xla: XlaCounts) -> dict:
    """The same queries through the serving front door, submitted
    together under two tenants."""
    import bench
    from spark_rapids_tpu.runtime import attribution, memory
    from spark_rapids_tpu.sql.server import QueryServer
    server = QueryServer(session)
    c0 = counters(xla)
    t0 = time.monotonic()
    handles = [
        (name, server.submit(
            lambda b=bench.TPCH_BUILDERS[name]: b(session, tables),
            tenant=TENANTS[i % len(TENANTS)]))
        for i, name in enumerate(names)]
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
    d = delta(counters(xla), c0)
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


def phase_compare(names, tables, direct: dict, served: dict) -> float:
    """Outside any timed window: every result against the CPU twin."""
    import bench
    from spark_rapids_tpu.sql.session import TpuSession
    twin = TpuSession({"spark.rapids.sql.enabled": False})
    worst = 0.0
    for name in names:
        t0 = time.monotonic()
        want = bench.TPCH_BUILDERS[name](twin, tables).toArrow()
        ref_s = time.monotonic() - t0
        for kind, got in (("direct", direct[name]), ("served", served[name])):
            cmp_ = compare_tables(got, want)
            emit("compare", query=name, against="cpu_twin", result_of=kind,
                 reference_s=ref_s, rtol=RTOL, **cmp_)
            require(cmp_["equal"],
                    f"{name} ({kind}) differs from the CPU twin: "
                    f"{cmp_['diffs']}")
            worst = max(worst, cmp_["max_rel_err"])
    return worst


def run_one_chip(args, session, devices, tables, xla, t_start) -> None:
    names = ["q6", "q1", args.join_query]
    direct = {n: run_direct(session, n, tables, xla)[0] for n in names}
    served = phase_served(session, names, tables, xla)
    total = counters(xla)
    pallas = int(total["dispatch_by_backend"].get("pallas", 0))
    emit("kernels", rungs_used={k: int(v) for k, v in
                                total["dispatch_by_backend"].items()},
         pallas_dispatches=pallas)
    if not args.rehearse:
        require(pallas > 0, "no dispatch ran on the pallas rung")
    worst = phase_compare(names, tables, direct, served)
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
    name = args.join_query
    got, df = run_direct(ici, name, tables, xla, phase="ici")
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
    want, _ = run_direct(one, name, tables, xla, phase="one_device")
    cmp_ = compare_tables(got, want)
    emit("compare", query=name, against="one_device",
         result_of="ici", rtol=RTOL, **cmp_)
    require(cmp_["equal"], f"ICI result differs from one device: "
                           f"{cmp_['diffs']}")
    total = counters(xla)
    emit("summary", wall_s=time.monotonic() - t_start,
         kernel_compile_s=total["kernel_compile_s"],
         xla_compile_requests=total["xla_compile_requests"],
         persistent_cache_hits=total["persistent_cache_hits"],
         xla_compiles=total["xla_compiles"],
         max_rel_err=cmp_["max_rel_err"],
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
    xla = XlaCounts()
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
    ap.add_argument("--join-query", default=JOIN_QUERY,
                    choices=("q3", "q12"),
                    help="the join query run after q6 and q1")
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
