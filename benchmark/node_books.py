"""Readers of the ledger's books by plan node (``nodes``: the sweep of
``stages_s`` and the ``counts`` again, keyed by each operator's
pre-order path in the plan, ``"0"``, ``"0.1"``, ...), for the metric
files of a cell whose query runs two operators of one class (session.q18:
two aggregates, three joins; session.q3: two joins).

A node is chosen by its place in the plan, never by a number the run
gives: of the nodes of one class the one nearest the root has the
shortest path, the deepest the longest.  Median a query over the ledgers
inside the window's answered requests, as ``book_readers`` takes them;
None, never an exception, where the program's ledger has no ``nodes``."""

from book_readers import _median


def _depth(path):
    return path.count(".")


def node(b, op, pick):
    """The books of one node of class ``op`` in ledger ``b``: ``pick``
    is ``min`` for the one nearest the root, ``max`` for the deepest
    (ties, which a plan of two such nodes at one depth would make, go to
    the first in pre-order).  None where the ledger has no such node."""
    mine = [p for p, n in b.get("nodes", {}).items() if n.get("op") == op]
    if not mine:
        return None
    ordered = sorted(mine, key=lambda p: [int(i) for i in p.split(".")])
    return b["nodes"][pick(ordered, key=_depth)]


def node_host_ms(run, op, pick):
    """Median milliseconds a query in the node's own stages, those keyed
    ``<op>:<stage>`` (a launch inside one is the kernel's, as in
    ``op_books.op_host_ms``)."""
    def value(b):
        n = node(b, op, pick)
        if n is None:
            return None
        return sum(s for k, s in n["stages_s"].items()
                   if k.split(":", 1)[0] == op)
    return _median(run, value, 1e3)


def node_count(run, op, pick, name):
    """Median of one of the node's ``counts`` a query; 0 where the node
    is there and counted no such thing."""
    def value(b):
        n = node(b, op, pick)
        return None if n is None else n["counts"].get(name, 0)
    return _median(run, value)
