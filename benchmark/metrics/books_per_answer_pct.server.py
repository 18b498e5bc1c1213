"""Share of the window's answered requests that have a ledger of their own
(server.throughput): 100 where every query in flight closes its own book.

A ledger belongs to the tightest answered request that encloses its wall
(a short query's ledger also lies inside the other stream's long request,
and a stream that is done counting keeps sending beside the other's last
pass); a request is counted once however many ledgers it encloses.
Nothing where the program publishes no ledger at all."""

from book_readers import _recent


def read(run):
    requests = run["requests"]
    ledgers = _recent()
    if not requests or not ledgers:
        return None
    own = set()
    for b in ledgers:
        around = [(r.t_done - r.t_submit, i) for i, r in enumerate(requests)
                  if r.t_submit <= b["t0_mono"] and b["t1_mono"] <= r.t_done]
        if around:
            own.add(min(around)[1])
    return 100.0 * len(own) / len(requests)
