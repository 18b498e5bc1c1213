"""Median milliseconds of a query's wall in the stages of the
``TpuSortMergeJoinExec`` nearest the root of the plan (session.q18: the
joined orders against ``lineitem`` streamed whole), by the ledger's
books by plan node; at most ``join_host_ms.q18``.  Nothing where the
ledger keeps no books by node."""

from node_books import node_host_ms


def read(run):
    return node_host_ms(run, "TpuSortMergeJoinExec", min)
