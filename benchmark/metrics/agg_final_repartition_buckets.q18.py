"""Buckets the repartition merge of the ``TpuHashAggregateExec`` nearest
the root cut its partials into, a query (session.q18: the five-key
group-by; its node's ``aggRepartitionBuckets`` in the ledger's books by
plan node, 0 where that merge did not repartition).  Nothing where the
ledger keeps no books by node."""

from node_books import node_count


def read(run):
    return node_count(run, "TpuHashAggregateExec", min,
                      "aggRepartitionBuckets")
