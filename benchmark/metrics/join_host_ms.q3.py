"""Median milliseconds of a query's wall in the stages of
``TpuSortMergeJoinExec`` by the ledger's ``stages_s`` (session.q3).
Nothing where the ledger keeps no books by operator."""

from op_books import op_host_ms


def read(run):
    return op_host_ms(run, "TpuSortMergeJoinExec")
