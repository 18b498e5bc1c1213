#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once, in one process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``): import the program, make the cell's tables from
the seed, open a ``TpuSession`` with the configuration's conf (and a
``QueryServer`` where the configuration's entry is ``server``), run
every binding of the mix once through the entry the window drives.
Then the window.  Then, outside both, the plain reference for every
binding and the comparison of every answer the window returned.

The last line of standard output is the result object; everything else
(one JSON object a line) comes before it.  The numbers compared, each
beside its limit, are the last lines of standard error and the last key
of the result.

Refuses any platform but ``tpu``.  ``--rehearse`` (never in
BENCHMARK.json) runs the same control flow at SF0.01 on whatever
backend is there, for ``benchmark/tests``; its ``device`` says so.
"""

import argparse
import importlib.util
import json
import math
import os
import sys
import tempfile
import threading
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REHEARSE_SF = 0.01


class Refused(Exception):
    """The run cannot be made here: no result is printed."""


def emit(phase: str, **kw) -> None:
    print(json.dumps({"phase": phase, **kw}), flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` by the name BENCHMARK.json or a
    traffic file gives; names may hold dots."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise Refused(f"no workload {name!r} in BENCHMARK.json; it has "
                  f"{[w['name'] for w in bench['workloads']]}")


def load_cell(name: str):
    """BENCHMARK.json, the cell's entry, its configuration's file, its
    traffic mix and the module of every query the mix binds."""
    bench = load_json("..", "BENCHMARK.json")
    cell = find_cell(bench, name)
    with open(os.path.join(ROOT, next(
            c["file"] for c in bench["configs"]
            if c["name"] == cell["config"]))) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    queries = {q: load_module("queries", q) for q in traffic["bindings"]}
    return bench, cell, config, traffic, queries


def tables_needed(queries: dict) -> dict:
    """relation -> the columns the queries name, each once, in order."""
    need = {}
    for q in queries.values():
        for rel, cols in q.TABLES.items():
            have = need.setdefault(rel, [])
            have += [c for c in cols if c not in have]
    return need


def metrics_of(entries, cell: str):
    return [m for m in entries if cell in m.get("workloads", [cell])]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of every request of the window."""
    v = sorted(values)
    return v[max(0, math.ceil(len(v) * q / 100) - 1)] if v else None


def end_to_end(name: str, streams, t0: float) -> float:
    """An end-to-end metric from its file ``end_to_end/<name>.json``:
    one of two statistics over the streams of one class; a percentile
    may name the ``queries`` whose requests it is taken over."""
    spec = load_json("end_to_end", name + ".json")
    mine = [s for s in streams if s.cls == spec["cls"]]
    done = [r for s in mine for r in s.requests
            if r.error is None and r.q in spec.get("queries", (r.q,))]
    if not done:
        return None
    if spec["stat"] == "stream_seconds_per_request":
        # every stream's time from the window's start to its last
        # completion, over all the requests they completed
        return sum(s.t_last_done - t0 for s in mine if s.requests) / len(done)
    if spec["stat"] == "percentile":
        return percentile([r.t_done - r.t_submit for r in done], spec["q"])
    raise Refused(f"end_to_end/{name}.json: unknown stat {spec['stat']!r}")


def traced_slice(spec: dict, streams, out: dict):
    """Returns ``during(t0)`` for ``run_window``: trace a slice of the
    window into a directory under TMPDIR, under a ``bench.slice`` span."""
    import glob

    import jax

    def during(t0: float) -> None:
        time.sleep(max(0.0, t0 + spec["start_s"] - time.monotonic()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        tmp = tempfile.mkdtemp(prefix="tpuq-bench-trace-")
        out["dir"] = tmp
        jax.profiler.start_trace(tmp, profiler_options=opts)
        t_s = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.slice"):
            while True:
                time.sleep(0.02)
                el = time.monotonic() - t_s
                whole = sum(1 for s in streams for r in s.requests[-64:]
                            if r.t_submit >= t_s)
                if el >= spec["max_s"] or (
                        el >= spec["min_s"] and whole >= spec["whole_queries"]):
                    break
        out["slice_host_s"] = time.monotonic() - t_s
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        out["xplane"] = found[0] if found else None
    return during


def run(args) -> dict:
    sys.path[:0] = [ROOT, HERE]
    bench, cell, config, traffic, queries = load_cell(args.workload)
    try:
        import jax
        import spark_rapids_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        raise Refused(f"the program is not in this checkout: {e}")
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if args.rehearse:
        info["rehearsal"] = f"SF{REHEARSE_SF}, not a measurement"
    elif info["platform"] != "tpu":
        raise Refused(f"platform is {info['platform']!r}, not 'tpu'")
    if len(devices) < cell["chips"]:
        raise Refused(f"the cell asks for {cell['chips']} chip(s), jax sees "
                      f"{len(devices)}")
    peaks = load_json("peaks.json")
    if not args.rehearse and info["kind"] not in peaks:
        raise Refused(f"device kind {info['kind']!r} is not in peaks.json")

    import compare
    import counters
    import loadgen
    import tpch_gen
    from spark_rapids_tpu.runtime import device as tpuq_device
    from spark_rapids_tpu.sql.session import TpuSession

    # ---- set-up -------------------------------------------------------
    sf = REHEARSE_SF if args.rehearse else config["scale_factor"]
    t = time.monotonic()
    tables = tpch_gen.gen_tables(sf, args.seed, tables_needed(queries))
    emit("data", sf=sf, seed=args.seed, gen_s=time.monotonic() - t,
         rows={k: v.num_rows for k, v in tables.items()},
         arrow_bytes=sum(v.nbytes for v in tables.values()))
    xla = counters.XlaCounts()
    session = TpuSession(dict(config["conf"]))
    annotate = (jax.profiler.TraceAnnotation if args.trace
                else loadgen.no_annotation)
    entry = loadgen.ENTRIES[config["entry"]](session, tables, queries,
                                             annotate)
    streams = loadgen.plan_streams(traffic, args.seed)
    bindings = traffic["bindings"]
    c0 = counters.snapshot(xla)
    warm = loadgen.warm_up(entry, streams, bindings)
    c1 = counters.snapshot(xla)
    setup_s = time.monotonic() - T_START
    emit("setup", setup_s=setup_s, device=info,
         compile_cache_dir=tpuq_device.cache_dir_in_force(),
         conf=config["conf"], entry=config["entry"],
         warm_requests=len(warm),
         warm_failed=[r.error for r in warm if r.error],
         warm_s={f"{r.q}[{r.binding}]": round(r.t_done - r.t_submit, 4)
                 for r in warm},
         **counters.delta(c1, c0))
    if any(r.error for r in warm):
        raise Refused(f"warm-up failed: {[r.error for r in warm if r.error]}")

    # ---- the window ---------------------------------------------------
    trace_out = {}
    during = (traced_slice(traffic["trace_slice"], streams, trace_out)
              if args.trace else None)
    t0 = loadgen.run_window(entry, streams, bindings, args.seconds, annotate,
                            during)
    c2 = counters.snapshot(xla)
    window = counters.delta(c2, c1)
    stats = [d.memory_stats() or {} for d in devices]
    info["memory_peak_bytes"] = max(
        int(s.get("peak_bytes_in_use", 0)) for s in stats)
    requests = [r for s in streams for r in s.requests]
    failed = [r for r in requests if r.error is not None]
    answered = [r for r in requests if r.error is None]
    unanswered = sum(1 for th in threading.enumerate()
                     if th.name.startswith("bench-stream-"))
    last_df = dict(entry.last_df)
    on_cpu = sum(int(df.fallback_summary()["fallback_ops"])
                 for df in last_df.values())
    entry.close()
    emit("window", seconds=args.seconds, attempted=len(requests),
         failed=len(failed), errors=sorted({r.error for r in failed})[:5],
         by_class={c: sum(1 for r in answered if r.cls == c)
                   for c in sorted({s.cls for s in streams})},
         by_query={q: sum(1 for r in answered if r.q == q) for q in queries},
         stream_s=[round(s.t_last_done - t0, 4) for s in streams],
         **window)
    # per-request records: every request of a stream of long queries,
    # the first and the slowest of a stream of short ones
    for s in streams:
        took = [round(r.t_done - r.t_submit, 4) for r in s.requests]
        emit("stream", index=s.index, cls=s.cls, requests=len(took),
             first_s=took[:48], slowest_s=sorted(took)[-8:],
             slowest_at=[i for i, _ in sorted(
                 enumerate(took), key=lambda x: x[1])[-8:]])

    # ---- metrics ------------------------------------------------------
    metrics = {}
    e2e = {"setup_s": setup_s}
    for m in metrics_of(bench["end_to_end"], cell["name"]):
        if m["name"] != "setup_s":
            e2e[m["name"]] = end_to_end(m["name"], streams, t0)
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    breakdown = None
    if args.trace:
        import shutil

        import trace_reduce
        reduced = None
        t = time.monotonic()
        if trace_out.get("xplane"):
            reduced = trace_reduce.reduce(trace_reduce.read_xplane(
                trace_out["xplane"], rehearse=args.rehearse))
        if trace_out.get("dir"):
            shutil.rmtree(trace_out["dir"], ignore_errors=True)
        run_ctx = {"trace": reduced, "window_counters": window,
                   "requests": answered, "streams": streams,
                   "peak": peaks.get(info["kind"]),
                   "min_bytes": {q: m.min_bytes(tables)
                                 for q, m in queries.items()}}
        for m in metrics_of(bench["per_layer"], cell["name"]):
            value = load_module("metrics", m["name"]).read(run_ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced:
            info["busy_s"], info["window_s"] = (reduced["busy_s"],
                                                reduced["window_s"])
            breakdown = reduced["breakdown"]
        emit("trace", reduce_s=time.monotonic() - t,
             slice_host_s=trace_out.get("slice_host_s"),
             end_to_end_of_this_traced_run=e2e,
             **{k: v for k, v in (reduced or {}).items()
                if k not in ("breakdown", "whole_queries")},
             whole_queries=len((reduced or {}).get("whole_queries", [])))
    else:
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in e2e.items() if v is not None}

    # ---- correct: every answer of the window against the reference ----
    del session, entry, last_df
    t = time.monotonic()
    want = {}
    worst, mismatches, what = 0.0, 0, []
    for r in answered:
        key = (r.q, r.binding)
        if key not in want:
            want[key] = queries[r.q].reference(tables, bindings[r.q][r.binding])
        c = compare.compare_tables(r.table, want[key])
        worst = max(worst, c["max_rel_err"])
        if c["exact_mismatches"]:
            mismatches += c["exact_mismatches"]
            if len(what) < 5:
                what.append(f"{r.q}[{r.binding}]: {c['what']}")
    g = config["guarantees"]
    checks = {
        "answers_compared": {"value": len(answered), "limit": 1,
                             "at_least": True},
        "unanswered": {"value": unanswered + len(failed), "limit": 0},
        "exact_mismatches": {"value": mismatches,
                             "limit": g["exact_mismatches"]},
        "max_rel_err": {"value": worst, "limit": g["double_rtol"]},
        "plan_nodes_on_cpu": {"value": on_cpu, "limit": 0},
        "host_degraded_ops": {"value": window["host_degraded_ops"],
                              "limit": 0},
        "breaker_trips": {"value": window["breaker_trips"], "limit": 0},
    }
    correct = compare.verdict(checks)
    emit("compare", reference_s=time.monotonic() - t,
         bindings_compared=len(want), mismatches=what)
    result = {"correct": correct, "attempted": len(requests),
              "failed": len(failed), "metrics": metrics, "device": info}
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="SF0.01 on whatever backend is there (tests)")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr, flush=True)
        return 4
    sys.stdout.flush()
    for name, c in result["checks"].items():
        word = ">=" if c.get("at_least") else "<="
        print(f"check {name}: {c['value']!r} {word} {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
