"""The one traffic generator: closed-loop streams over a cycle of
(query, binding) requests, through the entry the configuration names.

A traffic mix is data (``traffic/<name>.json``): groups of streams,
each with a class, a tenant, a count and the queries it cycles
through, and the bindings of every query.  The seed orders the
bindings of each stream; every seed sends the same set of requests.

Window edges: a stream stops *counting* when the window's seconds
have passed and its pass through its group's query list is complete;
what it has in flight is awaited and counted, and the stream's time
runs from the window's start to its last counted completion.  So every
stall inside the window is in the numerator of a seconds-per-request
metric, a window that holds a dozen long queries is not quantised by
the one it cuts, and a stream that cycles through long and short
queries finishes as many of the one as of the other.  In a mix of
several streams a stream that is done counting keeps sending, uncounted,
until the last stream is done too: no stream's last pass runs beside a
system emptier than the mix.
"""

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

RESULT_TIMEOUT_S = 120.0   # a request of the window that takes longer never came
WARM_TIMEOUT_S = 1100.0    # a checkout's first run compiles in its warm-up


@dataclass
class Request:
    stream: int
    cls: str
    q: str
    binding: int
    t_submit: float = 0.0
    t_done: float = 0.0
    table: object = None
    queue_wait_s: Optional[float] = None
    error: Optional[str] = None


@dataclass
class Stream:
    index: int
    cls: str
    tenant: str
    cycle: List[tuple]                    # (query name, binding index)
    pass_len: int = 1                     # queries in the group's list
    requests: List[Request] = field(default_factory=list)
    t_last_done: float = 0.0


def plan_streams(traffic: dict, seed: int) -> List[Stream]:
    """Every stream's cycle: pass after pass through its group's query
    list, each query's bindings in an order of the stream's own drawn
    from the seed."""
    streams = []
    for group in traffic["streams"]:
        queries = group["queries"]
        for _ in range(group.get("count", 1)):
            index = len(streams)
            rng = np.random.default_rng([seed, 1000 + index])
            order = {q: rng.permutation(len(traffic["bindings"][q]))
                     for q in queries}
            longest = max(len(o) for o in order.values())
            cycle = [(q, int(order[q][p % len(order[q])]))
                     for p in range(longest) for q in queries]
            streams.append(Stream(index, group["cls"],
                                  group.get("tenant", "default"), cycle,
                                  len(queries)))
    return streams


class SessionEntry:
    """A Spark job: ``build(...).toArrow()`` on the caller's thread."""

    def __init__(self, session, tables, queries, annotate):
        self.session, self.tables, self.queries = session, tables, queries
        self.annotate = annotate
        self.last_df = {}

    def run(self, req: Request, tenant: str, binding: dict) -> None:
        df = self.queries[req.q].build(self.session, self.tables, binding)
        with self.annotate("bench.toArrow", q=req.q):
            req.table = df.toArrow()
        self.last_df[(req.q, req.binding)] = df

    def close(self) -> None:
        pass


class ServerEntry:
    """A shared warehouse: ``QueryServer.submit(callable, tenant=...)``
    then ``result()``, the plan built on the admitted worker."""

    def __init__(self, session, tables, queries, annotate):
        from spark_rapids_tpu.sql.server import QueryServer
        self.session, self.tables, self.queries = session, tables, queries
        self.annotate = annotate
        self.last_df = {}
        self.result_timeout_s = RESULT_TIMEOUT_S
        self.server = QueryServer(session)

    def run(self, req: Request, tenant: str, binding: dict) -> None:
        def make():
            df = self.queries[req.q].build(self.session, self.tables, binding)
            self.last_df[(req.q, req.binding)] = df
            return df
        with self.annotate("bench.submit", q=req.q):
            handle = self.server.submit(make, tenant=tenant)
        with self.annotate("bench.result", q=req.q):
            req.table = self.server.result(
                handle, timeout_s=self.result_timeout_s)
        req.queue_wait_s = handle.queue_wait_s

    def close(self) -> None:
        self.server.shutdown()


def no_annotation(name, **kw):
    return contextlib.nullcontext()


ENTRIES = {"session": SessionEntry, "server": ServerEntry}


def one_request(entry, stream: Stream, q: str, bi: int, bindings: dict,
                annotate) -> Request:
    req = Request(stream.index, stream.cls, q, bi)
    req.t_submit = time.monotonic()
    try:
        with annotate("bench.query", q=q, cls=stream.cls):
            entry.run(req, stream.tenant, bindings[q][bi])
    except Exception as e:          # counted as failed, the stream goes on
        req.error = f"{type(e).__name__}: {e}"[:300]
    req.t_done = time.monotonic()
    return req


def warm_up(entry, streams: List[Stream], bindings: dict) -> List[Request]:
    """Every (query, binding) of the mix once, through the same entry
    and under the tenant that sends it, one at a time."""
    seen, out = set(), []
    entry.result_timeout_s = WARM_TIMEOUT_S
    for st in streams:
        for q, bi in st.cycle:
            if (st.tenant, q, bi) in seen:
                continue
            seen.add((st.tenant, q, bi))
            out.append(one_request(entry, st, q, bi, bindings,
                                   no_annotation))
    entry.result_timeout_s = RESULT_TIMEOUT_S
    return out


def run_window(entry, streams: List[Stream], bindings: dict, seconds: float,
               annotate, during: Optional[Callable] = None) -> float:
    """Drive every stream for ``seconds``; ``during(t0)`` runs on the
    caller's thread meanwhile (the traced slice).  Returns the window's
    start on ``time.monotonic()``."""
    go = threading.Event()
    t0_box = []
    counting = [len(streams)]       # streams not yet done counting
    lock = threading.Lock()

    def loop(st: Stream):
        go.wait()
        t0 = t0_box[0]
        i = 0
        while time.monotonic() - t0 < seconds or i % st.pass_len:
            q, bi = st.cycle[i % len(st.cycle)]
            req = one_request(entry, st, q, bi, bindings, annotate)
            st.requests.append(req)
            st.t_last_done = req.t_done
            i += 1
        with lock:
            counting[0] -= 1
        while counting[0]:          # load for the others, not counted
            q, bi = st.cycle[i % len(st.cycle)]
            one_request(entry, st, q, bi, bindings, annotate)
            i += 1

    threads = [threading.Thread(target=loop, args=(st,),
                                name=f"bench-stream-{st.index}", daemon=True)
               for st in streams]
    for t in threads:
        t.start()
    t0_box.append(time.monotonic())
    go.set()
    if during is not None:
        during(t0_box[0])
    deadline = t0_box[0] + seconds + RESULT_TIMEOUT_S + 5
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    return t0_box[0]
