"""The reduction from a trace to what the readers read: the interval
arithmetic on made-up intervals, and the whole reduction on a small
trace recorded on the chip (``data/q6_slice.xplane.pb``: ``session.q6``
on one TPU v5 lite, a slice of a fraction of a second, PR 25)."""

import os

import pytest

import trace_reduce as tr

from conftest import HERE

RECORDED = os.path.join(HERE, "data", "q6_slice.xplane.pb")


def test_union_covered_gaps():
    u = tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5), (6, 7)])
    assert u == [(0, 2.5), (3, 4), (6, 7)]
    assert tr.covered(u, 0, 10) == pytest.approx(4.5)
    assert tr.covered(u, 1, 3.5) == pytest.approx(2.0)
    assert tr.covered(u, 4, 6) == 0
    assert tr.gaps(u, 0, 8) == [(2.5, 3), (4, 6), (7, 8)]
    assert tr.gaps(u, 1, 3.5) == [(2.5, 3)]
    assert tr.gaps([], 0, 1) == [(0, 1)]


def _raw(ops, launches, spans, host=()):
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "launches": launches}],
            "spans": spans, "host": list(host)}


def test_reduce_on_a_made_up_trace():
    spans = [
        {"name": "bench.slice", "start": 10.0, "end": 20.0, "thread": "main"},
        # cut by the slice's start: not a whole query
        {"name": "bench.query", "start": 9.0, "end": 11.0, "thread": "s",
         "q": "q6", "cls": "query"},
        {"name": "bench.query", "start": 12.0, "end": 16.0, "thread": "s",
         "q": "q6", "cls": "query"},
        {"name": "bench.toArrow", "start": 12.0, "end": 16.0, "thread": "s",
         "q": "q6"},
    ]
    ops = [("fusion", 9.5, 10.5), ("fusion", 12.5, 13.0), ("sort", 13.0, 14.0),
           ("sort", 15.0, 15.5), ("fusion", 19.5, 21.0)]
    launches = [("jit_a", 9.5, 10.5), ("jit_a", 12.5, 14.0),
                ("jit_b", 15.0, 15.5), ("jit_a", 19.5, 21.0)]
    host = [("PjitFunction(run)", 14.0, 15.0), ("outer", 11.0, 19.0)]
    r = tr.reduce(_raw(ops, launches, spans, host))
    assert r["window_s"] == pytest.approx(10.0)
    # clipped to the slice: 0.5 + 0.5 + 1 + 0.5 + 0.5
    assert r["busy_s"] == pytest.approx(3.0)
    assert len(r["whole_queries"]) == 1
    q = r["whole_queries"][0]
    assert q["launches"] == 2 and q["busy_s"] == pytest.approx(2.0)
    assert q["launch_gaps_s"] == [pytest.approx(1.0)]
    assert r["breakdown"]["device_ops"][0] == ["fusion x3", pytest.approx(1.5)]
    labels = dict(r["breakdown"]["idle_gaps"])
    # the gap 14..15 lies in toArrow, under the host's PjitFunction
    assert labels["toArrow:q6 | PjitFunction(run)"] == pytest.approx(1.0)
    assert sum(labels.values()) == pytest.approx(7.0)


def test_op_class_drops_numbers_and_layouts():
    assert tr.op_class(
        "%fusion.65 = f32[1048576]{0:T(1024)} fusion(f32[1048576]{0:T(1024)} "
        "%get-tuple-element.394, s32[1048576]{0:T(1024)S(1)} %copy-done.13), "
        "kind=kCustom, calls=%fused_computation.23.clone"
    ) == "fusion[kCustom] -> f32[1048576]"
    assert tr.op_class(
        "%fusion.3 = (f32[]{:T(128)}, f32[]{:T(128)}) fusion(pred[1048576]"
        "{0:T(1024)(128)(4,1)S(1)} %get-tuple-element.36), kind=kLoop, "
        "calls=%fused_computation.7") == "fusion[kLoop] -> (f32[], f32[])"
    assert tr.op_class(
        '%custom-call = f32[1048576]{0:T(1024)S(1)} custom-call(f64[1048576]'
        '{0:T(1024)} %b_0__2__0_.1), custom_call_target="X64SplitHigh"'
    ) == "custom-call[X64SplitHigh] -> f32[1048576]"
    assert tr.op_class("sort.22") == "sort.22"


def test_nothing_to_read_gives_none():
    assert tr.reduce(_raw([], [], [])) is None
    assert tr.reduce(_raw([("x", 0, 1)], [], [])) is None   # no slice span


def test_only_a_rehearsal_takes_host_events_for_a_devices(tmp_path):
    """A trace with no ``/device:TPU`` plane (made here on the CPU): a
    rehearsal reads the host plane's XLA events in the device's place,
    a run that is no rehearsal reads no device and reports nothing."""
    import glob

    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2 + 1).sum())
    f(jnp.arange(1 << 16)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.slice"):
        for _ in range(20):
            f(jnp.arange(1 << 16)).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert tr.read_xplane(path)["devices"] == []
    assert tr.reduce(tr.read_xplane(path)) is None
    stand_in = tr.read_xplane(path, rehearse=True)
    assert stand_in["devices"] and tr.reduce(stand_in)["busy_s"] > 0


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_reduce_on_the_recorded_chip_trace():
    raw = tr.read_xplane(RECORDED)
    assert [d["name"] for d in raw["devices"]] == ["/device:TPU:0"]
    r = tr.reduce(raw)
    # pinned: a change of the reduction shows here before on the chip
    assert r["window_s"] == pytest.approx(0.120792789, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.004692692, rel=1e-6)
    assert (r["ops"], r["launches"]) == (2412, 114)
    assert len(r["whole_queries"]) == 11
    assert all(q["q"] == "q6" and q["launches"] == 10
               for q in r["whole_queries"])
    assert r["whole_queries"][0]["busy_s"] == pytest.approx(0.000425332,
                                                            rel=1e-5)
    top, seconds = r["breakdown"]["device_ops"][0]
    assert top.startswith("custom-call[X64Split") and seconds > 0.001
    assert r["breakdown"]["idle_gaps"][0][0].startswith("toArrow:q6 | ")
    # and the readers over it
    from run import load_module
    run = {"trace": r, "peak": {"hbm_gb_per_s": 819.0},
           "min_bytes": {"q6": 168_000_000}}
    idle = load_module("metrics", "device_idle_pct.session").read(run)
    assert idle == pytest.approx(96.115, abs=0.01)
    roof = load_module("metrics", "hbm_roofline_pct.session").read(run)
    assert roof == pytest.approx(48.26, abs=0.05)
    assert load_module("metrics", "launches_per_query.session").read(run) == 10
    gap = load_module("metrics", "launch_gap_ms.session").read(run)
    assert 0.3 < gap < 1.5
    # a reader with nothing to read returns nothing, never 0
    empty = {"trace": None, "peak": None, "min_bytes": {}}
    for name in ("device_idle_pct.session", "hbm_roofline_pct.session",
                 "launches_per_query.session", "launch_gap_ms.session"):
        assert load_module("metrics", name).read(empty) is None
