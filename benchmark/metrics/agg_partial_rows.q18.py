"""Live partial rows handed to the aggregates' merges, a query
(session.q18): ``counts.aggPartialRows`` of the ledger, the counts
``_merge_bounded`` pulls before it decides between one concat and the
repartition.  Nothing where the ledger keeps no such count."""

from op_books import count


def read(run):
    return count(run, "aggPartialRows")
