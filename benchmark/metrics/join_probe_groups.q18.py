"""Probe groups a query (session.q18): calls of the join's match kernel
with a probe, over the three joins (``counts.joinProbeGroups`` of the
ledger).  Nothing where the ledger keeps no counts."""

from op_books import count


def read(run):
    return count(run, "joinProbeGroups")
