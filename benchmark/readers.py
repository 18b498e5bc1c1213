"""What the per-layer metrics' readers share.  A metric is a file of
its own, ``metrics/<name>.py``, whose ``read(run)`` is one of these
(or code of its own): the same quantity has one metric for each
end-to-end metric it moves, since cells that report different
end-to-end metrics may not share a per-layer one.

``run`` holds ``trace`` (what ``trace_reduce.reduce`` returns, or
None), ``window_counters``, ``requests``, ``streams``, ``peak`` and
``min_bytes`` by query.  A reader that finds nothing to read returns
None, never 0."""

import statistics


def device_idle_pct(run):
    """Share of the traced slice in which no operation ran on the device."""
    t = run["trace"]
    if not t or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def hbm_roofline_pct(run):
    """The least time the chip's HBM needs to read what a query must
    read once (``queries/<q>.py::min_bytes``: the referenced columns at
    their Arrow widths), over the seconds the device was busy in that
    query, averaged over the whole queries of the traced slice.  It
    counts the query's work, not any kernel's, so it reads the same
    whatever implements the query."""
    t, peak = run["trace"], run["peak"]
    if not t or not peak:
        return None
    shares = []
    for q in t["whole_queries"]:
        if q["busy_s"] > 0 and q["q"] in run["min_bytes"]:
            least_s = run["min_bytes"][q["q"]] / (peak["hbm_gb_per_s"] * 1e9)
            shares.append(least_s / q["busy_s"])
    return 100.0 * sum(shares) / len(shares) if shares else None


def launches_per_query(run):
    """XLA program executions on the device inside one query, averaged
    over the whole queries of the traced slice (``XLA Modules``)."""
    t = run["trace"]
    if not t or not t["whole_queries"]:
        return None
    return (sum(q["launches"] for q in t["whole_queries"])
            / len(t["whole_queries"]))


def launch_gap_ms(run):
    """Median idle time between the end of one program on the device
    and the start of the next inside one query, over the whole queries
    of the traced slice: what the exec pumps take to hand the device
    its next program."""
    t = run["trace"]
    if not t:
        return None
    between = [g for q in t["whole_queries"] for g in q["launch_gaps_s"]]
    return 1e3 * statistics.median(between) if between else None


def compiles_in_window(run):
    """Backend compiles jax ran plus kernels the program's cache
    compiled, over the whole window (expected 0: set-up warms every
    binding)."""
    c = run["window_counters"]
    return float(c["xla_compiles"] + c["kernel_compiles"])


def queue_wait_ms(run):
    """Mean ``QueryHandle.queue_wait_s`` of the window's requests that
    went through ``QueryServer``: the time a query waited for a run
    slot."""
    waits = [r.queue_wait_s for r in run["requests"]
             if r.queue_wait_s is not None]
    return 1e3 * sum(waits) / len(waits) if waits else None
