"""The per-layer metrics read from the program's own time books.

The program closes a ledger a query (``runtime/attribution.py``): its
spans swept into exclusive buckets that add up, with an explicit
``unaccounted``, to the query's wall, and published to a bounded ring,
``attribution.recent()``, stamped on ``time.monotonic()`` — the clock
``loadgen`` stamps requests with.  A reader here takes the ledgers whose
wall lies inside an answered request of the window (warm-up and
uncounted requests are left out) and returns the median a query, or
None where it finds no ledger: a program without the ring, attribution
switched off, or a bucket the program does not keep.

As in ``readers.py``, one quantity has a metric file for each
end-to-end metric it moves (``.session``, ``.scan``)."""

import bisect
import statistics


def books(run) -> list:
    """The published ledgers that lie inside an answered request."""
    if "_books" not in run:
        run["_books"] = _inside(_recent(), run["requests"])
    return run["_books"]


def _recent() -> list:
    try:
        from spark_rapids_tpu.runtime import attribution
    except ImportError:
        return []
    recent = getattr(attribution, "recent", None)
    return recent() if recent is not None else []


def _inside(ledgers, requests) -> list:
    spans = sorted((r.t_submit, r.t_done) for r in requests)
    starts = [s for s, _ in spans]
    # the latest end among the requests begun no later than each one
    ends, last = [], float("-inf")
    for _, e in spans:
        last = max(last, e)
        ends.append(last)
    out = []
    for b in ledgers:
        i = bisect.bisect_right(starts, b["t0_mono"]) - 1
        if i >= 0 and b["t1_mono"] <= ends[i]:
            out.append(b)
    return out


def _median(run, value, scale=1.0):
    vals = [v for v in map(value, books(run)) if v is not None]
    return scale * statistics.median(vals) if vals else None


def _bucket_ms(run, *names):
    """Median milliseconds a query in the named buckets together."""
    def value(b):
        got = [b["buckets"].get(n) for n in names]
        return None if None in got else sum(got)
    return _median(run, value, 1e3)


def plan_ms(run):
    """Logical optimization, physical planning and the overrides."""
    return _bucket_ms(run, "plan")


def launch_host_ms(run):
    """Host time inside cached-kernel calls."""
    return _bucket_ms(run, "kernel_launch")


def pump_host_ms(run):
    """Exec code and iterator plumbing around the launches."""
    return _bucket_ms(run, "kernel_dispatch", "pump_idle")


def result_d2h_ms(run):
    """The result leaving the device (where a device-bound query's host
    waits), the root's Arrow conversion and concat."""
    return _bucket_ms(run, "result_d2h")


def epilogue_ms(run):
    """What the always-on books and the event log cost the caller after
    the answer exists (``record_s``, outside the ledger's wall)."""
    return _median(run, lambda b: b.get("record_s"), 1e3)


def cached_launches_per_query(run):
    """Cached-kernel calls a query; ``launches_per_query`` from the
    device less this is what was launched outside the kernel cache."""
    return _median(run, lambda b: b.get("launches"))


def books_unaccounted_pct(run):
    """Share of a query's wall no span claimed: the check on the books."""
    return _median(run, lambda b: (b["unaccounted_s"] / b["e2e_s"]
                                   if b.get("e2e_s") else None), 100.0)
