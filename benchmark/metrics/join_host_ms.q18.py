"""Median milliseconds of a query's wall in the stages of
``TpuSortMergeJoinExec`` by the ledger's ``stages_s`` (session.q18: the
left-semi join, the in-core join and the join that streams lineitem).
Nothing where the ledger keeps no books by operator."""

from op_books import op_host_ms


def read(run):
    return op_host_ms(run, "TpuSortMergeJoinExec")
