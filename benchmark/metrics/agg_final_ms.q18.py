"""Median milliseconds of a query's wall in the stages of the
``TpuHashAggregateExec`` nearest the root of the plan (session.q18: the
five-key group-by over the joined rows), by the ledger's books by plan
node.  Nothing where the ledger keeps no books by node."""

from node_books import node_host_ms


def read(run):
    return node_host_ms(run, "TpuHashAggregateExec", min)
