"""Batches a query's grouped aggregates folded sort-free into a running
accumulator of the first batch's groups (session.q1: a Q1 over 6 M rows
in 6 batches): ``counts.aggSortFreeBatches`` of the ledger,
``TpuHashAggregateExec._sort_free_stream``'s count.  Nothing where no
aggregate of the query took the sort-free pass, or the ledger keeps no
such count."""

from op_books import count


def read(run):
    return count(run, "aggSortFreeBatches")
