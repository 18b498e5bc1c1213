"""Median milliseconds of a query's wall in the stages of the deepest
``TpuHashAggregateExec`` of the plan (session.q18: the sub-aggregate,
``lineitem`` rolled up by ``l_orderkey`` into 1.47 M groups through the
repartition merge), by the ledger's books by plan node.  With
``agg_final_ms.q18`` it adds up to ``agg_host_ms.q18``.  Nothing where
the ledger keeps no books by node."""

from node_books import node_host_ms


def read(run):
    return node_host_ms(run, "TpuHashAggregateExec", max)
