"""Slots the joins' probes were given on the streamed side over the
live rows among them, a query (session.q3): 1 is a probe cut to its
live rows.  Nothing where the ledger keeps no counts."""

from op_books import ratio


def read(run):
    return ratio(run, "joinSlotsProbed", "joinLiveRowsStreamed")
