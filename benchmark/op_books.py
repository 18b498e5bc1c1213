"""Readers of the ledger's books by operator (``stages_s``: the
exclusive sweep keyed ``<op>:<stage>`` before it collapses into
buckets; ``counts``: what the operators of one query counted on the
host), for the metric files of a cell that runs several operators in
one query (session.q3).  Median a query over the ledgers inside the window's
answered requests, as ``book_readers`` takes them; None, never an
exception, where the program's ledger has no such key."""

from book_readers import _median


def count(run, name):
    """Median of one of the ledger's ``counts`` a query."""
    return _median(run, lambda b: b.get("counts", {}).get(name))


def ratio(run, over, under):
    """Median a query of one count over another."""
    def value(b):
        c = b.get("counts", {})
        return c[over] / c[under] if c.get(over) and c.get(under) else None
    return _median(run, value)


def op_host_ms(run, op):
    """Median milliseconds a query in the stages of one operator: the
    host's time no more specific span claimed (a launch call inside an
    operator's stage is the kernel's, a child operator's stage the
    child's)."""
    def value(b):
        mine = [s for k, s in b.get("stages_s", {}).items()
                if k.split(":", 1)[0] == op]
        return sum(mine) if mine else None
    return _median(run, value, 1e3)
