"""Median milliseconds of a query's wall inside the split of the
aggregate's repartition merge (session.q18): the ledger's ``stages_s``
of ``TpuHashAggregateExec:repartitionTime``.  Nothing where the ledger
has no such stage."""

from book_readers import _median


def read(run):
    return _median(run, lambda b: b.get("stages_s", {}).get(
        "TpuHashAggregateExec:repartitionTime"), 1e3)
