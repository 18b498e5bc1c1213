"""Median milliseconds of a query's wall in the stages of the
``TpuSortMergeJoinExec`` nearest the root of the plan (session.q3: the
second join, the customers' orders against ``lineitem`` streamed), by
the ledger's books by plan node; at most ``join_host_ms.q3``.  Nothing
where the ledger keeps no books by node."""

from node_books import node_host_ms


def read(run):
    return node_host_ms(run, "TpuSortMergeJoinExec", min)
