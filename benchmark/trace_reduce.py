"""From a profiler trace (``.xplane.pb``) to the few things the
per-layer metrics read.  jax only (``jax.profiler.ProfileData``).

What a trace of this benchmark holds:

* one plane a chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has an
  event for every operation that ran on the chip and whose line
  ``XLA Modules`` has one for every program execution (a launch);
* the host plane ``/host:CPU`` with one line a thread; the benchmark's
  own ``jax.profiler.TraceAnnotation`` spans are there under names that
  start with ``bench.``: ``bench.slice`` (the traced slice itself, main
  thread), ``bench.query`` (one request, on the stream's thread, with
  the stats ``q`` and ``cls``) and, inside it, ``bench.toArrow`` or
  ``bench.submit`` / ``bench.result``.

All planes of one trace share a clock, so a device operation can be
laid against the host span that was open when it ran.  Everything here
is clipped to ``bench.slice``.

A CPU rehearsal (``rehearse=True``, from ``run.py --rehearse`` alone)
has no device plane: operations are then the host plane's events that
carry an ``hlo_op`` stat and launches its ``PjRtCpuExecutable::Execute``
events, so that the readers can be driven in tests.  Nothing read that
way is a device number, and ``run.py`` marks the device as a
rehearsal.  A run that is no rehearsal reads device planes only: a
trace without one gives no operations, and the readers report nothing.
"""

import bisect
import re
from typing import List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
SLICE, QUERY = "bench.slice", "bench.query"
CPU_LAUNCH = "PjRtCpuExecutable::Execute"
LOOK_BACK = 4000

Interval = Tuple[float, float]          # seconds on the trace's clock


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(disjoint: List[Interval], lo: float, hi: float) -> float:
    """Length of ``disjoint`` (sorted, disjoint) inside [lo, hi]."""
    i = bisect.bisect_left(disjoint, (lo, lo))
    if i and disjoint[i - 1][1] > lo:
        i -= 1
    total = 0.0
    for s, e in disjoint[i:]:
        if s >= hi:
            break
        total += min(e, hi) - max(s, lo)
    return total


def gaps(disjoint: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``disjoint`` leaves uncovered."""
    out, at = [], lo
    for s, e in disjoint:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


_HLO = re.compile(r"^%[\w.\-]+ = (\([^)]*\)|\S+) ([\w\-]+)\(")
_KIND = re.compile(r"kind=(\w+)|custom_call_target=\"([\w.$]+)\"")
_LAYOUT = re.compile(r"\{[^}]*\}")


def op_class(text: str) -> str:
    """An XLA op's event name is its whole HLO line.  Its class is the
    opcode with its fusion kind or call target and the result's shape:
    the numbered names (``%fusion.65``) differ from program to program,
    the classes add up."""
    m = _HLO.match(_LAYOUT.sub("", text))
    if not m:
        return text[:120]
    kind = _KIND.search(text)
    what = m.group(2) + (f"[{kind.group(1) or kind.group(2)}]" if kind else "")
    return f"{what} -> {m.group(1)}"[:120]


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e


def _clip(items, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in items
            if e > lo and s < hi]


def read_xplane(path: str, rehearse: bool = False) -> dict:
    """The raw picture: operations and launches of each device, the
    ``bench.`` spans, and the other host events (for labelling gaps).
    Only a rehearsal takes the host plane's XLA events for a device's."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    stand_in = rehearse and not any(DEVICE_PLANE.match(p.name)
                                    for p in planes)
    devices, spans, host = [], [], []
    cpu_ops, cpu_launches = [], []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"name": plane.name, "ops": [], "launches": []}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    key = "ops" if line.name == OPS_LINE else "launches"
                    dev[key] = [(n, s, e) for n, s, e, _ in _events(line)]
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for i, line in enumerate(plane.lines):
                for n, s, e, ev in _events(line):
                    if n.startswith("bench."):
                        # threads of one process share a line name
                        spans.append({"name": n, "start": s, "end": e,
                                      "thread": f"{line.name}#{i}",
                                      **dict(ev.stats)})
                    elif e <= s:
                        continue
                    elif stand_in and "hlo_op" in dict(ev.stats):
                        cpu_ops.append((n, s, e))
                    else:
                        if stand_in and n == CPU_LAUNCH:
                            cpu_launches.append((n, s, e))
                        host.append((n, s, e))
    if stand_in and cpu_ops:
        devices = [{"name": "cpu rehearsal (host plane)", "ops": cpu_ops,
                    "launches": cpu_launches}]
    return {"devices": devices, "spans": spans, "host": host}


def _spans_by_thread(spans) -> dict:
    """thread -> (starts, spans), sorted by start, without the slice."""
    out = {}
    for sp in sorted((s for s in spans if s["name"] != SLICE),
                     key=lambda s: s["start"]):
        starts, sps = out.setdefault(sp["thread"], ([], []))
        starts.append(sp["start"])
        sps.append(sp)
    return out


def _open_spans(mid: float, by_thread: dict) -> str:
    """The innermost ``bench.`` span of every thread open at ``mid``
    (``query`` alone is the request outside ``toArrow``: building the
    plan; a request begun before the slice has no span in it)."""
    names = set()
    for starts, sps in by_thread.values():
        i = bisect.bisect_right(starts, mid)
        # spans of one thread nest: the last begun that is still open
        for sp in reversed(sps[max(0, i - 16):i]):
            if sp["end"] > mid:
                names.add(sp["name"][len("bench."):] + ":"
                          + str(sp.get("q", "?")))
                break
    return "+".join(sorted(names)) or "no_span_open"


def _label(mid_lo: float, mid_hi: float, by_thread, host_sorted, host_starts):
    """What the host was doing across an idle gap: the open ``bench.``
    spans at its middle, then the host event that overlaps it most (the
    shortest such, so the innermost)."""
    bench = _open_spans((mid_lo + mid_hi) / 2, by_thread)
    best, best_key = None, (0.0, 0.0)
    hi_i = bisect.bisect_left(host_starts, mid_hi)
    # the events that started last before the gap's end: an event that
    # began more than LOOK_BACK events earlier is an outer frame
    for n, s, e in host_sorted[max(0, hi_i - LOOK_BACK):hi_i]:
        if e <= mid_lo:
            continue
        over = min(e, mid_hi) - max(s, mid_lo)
        key = (round(over, 7), -(e - s))
        if key > best_key:
            best, best_key = n, key
    return f"{bench} | {best}" if best else bench


def reduce(raw: dict, label_longest: int = 300) -> Optional[dict]:
    """Clip to ``bench.slice`` and reduce.  None where the trace holds
    no slice span or no device operation: the readers then have nothing
    to read."""
    slices = [s for s in raw["spans"] if s["name"] == SLICE]
    if not slices or not any(d["ops"] for d in raw["devices"]):
        return None
    lo, hi = slices[0]["start"], slices[0]["end"]
    window = hi - lo
    busy_each, op_time, launches_each = [], {}, []
    for dev in raw["devices"]:
        ops = _clip(dev["ops"], lo, hi)
        busy_each.append(union([(s, e) for _, s, e in ops]))
        for n, s, e in ops:
            t = op_time.setdefault(op_class(n), [0.0, 0])
            t[0] += e - s
            t[1] += 1
        launches_each.append(sorted(
            (s, e, n) for n, s, e in _clip(dev["launches"], lo, hi)))
    n_dev = len(raw["devices"])
    busy_s = sum(covered(b, lo, hi) for b in busy_each) / n_dev
    # one stream of requests can be laid against the device; with
    # several, whose operation is whose is not in the trace
    busy0, launches0 = busy_each[0], launches_each[0]
    starts0 = [s for s, _, _ in launches0]
    queries = []
    for sp in raw["spans"]:
        if sp["name"] != QUERY or sp["start"] < lo or sp["end"] > hi:
            continue
        a = bisect.bisect_left(starts0, sp["start"])
        b = bisect.bisect_left(starts0, sp["end"])
        mine = launches0[a:b]
        between = [mine[i + 1][0] - mine[i][1] for i in range(len(mine) - 1)]
        queries.append({"q": sp.get("q"), "cls": sp.get("cls"),
                        "seconds": sp["end"] - sp["start"],
                        "busy_s": covered(busy0, sp["start"], sp["end"]),
                        "launches": len(mine),
                        "launch_gaps_s": [g for g in between if g > 0]})
    idle = sorted(gaps(busy0, lo, hi), key=lambda g: g[0] - g[1])
    host_sorted = sorted(raw["host"], key=lambda x: x[1])
    host_starts = [s for _, s, _ in host_sorted]
    by_thread = _spans_by_thread(raw["spans"])
    by_label = {}
    for s, e in idle[:label_longest]:
        name = _label(s, e, by_thread, host_sorted, host_starts)
        by_label[name] = by_label.get(name, 0.0) + (e - s)
    # the many short gaps: by the open spans alone, which is cheap
    for s, e in idle[label_longest:]:
        name = _open_spans((s + e) / 2, by_thread) + " | shorter gaps"
        by_label[name] = by_label.get(name, 0.0) + (e - s)
    top = lambda d, k: sorted(d.items(), key=k, reverse=True)[:10]
    return {
        "window_s": window, "busy_s": busy_s, "devices": n_dev,
        "ops": sum(c for _, c in op_time.values()),
        "launches": sum(len(x) for x in launches_each),
        "whole_queries": queries,
        "idle_gaps_count": len(idle),
        "longest_gap_s": (idle[0][1] - idle[0][0]) if idle else 0.0,
        "breakdown": {
            "device_ops": [[f"{n} x{c}", t] for n, (t, c) in
                           top(op_time, lambda kv: kv[1][0])],
            "idle_gaps": [[n, t] for n, t in top(by_label, lambda kv: kv[1])],
        },
    }


def describe(path: str, limit: int = 12) -> str:
    """Planes, lines and first events of a trace, for reading one by
    hand before trusting the reduction."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            ev = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(ev)}")
            for e in ev[:limit]:
                out.append(f"    {e.name[:80]} start_ns={e.start_ns:.0f} "
                           f"dur_ns={e.duration_ns:.0f} "
                           f"{dict(list(e.stats)[:6])}")
    return "\n".join(out)
