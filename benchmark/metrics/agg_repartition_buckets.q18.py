"""Buckets the aggregate's repartition merge cut its partials into, a
query (session.q18): ``counts.aggRepartitionBuckets`` of the ledger, the
``k`` of ``TpuHashAggregateExec._merge_bounded``'s fallback.  Nothing
where no merge of the query repartitioned, or the ledger keeps no such
count."""

from op_books import count


def read(run):
    return count(run, "aggRepartitionBuckets")
