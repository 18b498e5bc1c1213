"""Median milliseconds of a query's wall in the stages of
``TpuHashAggregateExec`` by the ledger's ``stages_s`` (session.q18: the
sub-aggregate with its repartition merge, and the final group-by).
Nothing where the ledger keeps no books by operator."""

from op_books import op_host_ms


def read(run):
    return op_host_ms(run, "TpuHashAggregateExec")
