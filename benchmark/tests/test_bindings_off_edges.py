"""Every binding of ``q14_stream`` keeps the filtered probe away from a
power of two, on every seed counted.

The program sizes a join's probe by its live rows, in power-of-two
buckets.  A month whose line items fall within a few per cent of a
power of two puts the probe in one bucket or the next by the seed's
data, and the cell's time then takes one of two values by the seed
(February 1995: 65.3-66.1 k rows about the 65 536-slot bucket, a 5-6 %
step).  So every binding has to keep ``MARGIN`` from every power of
two; February 1995 is the control and fails it.

Counts ``l_shipdate`` alone at SF1 (about a second a seed).  Run by
hand with the rest of ``benchmark/tests``, not in tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_bindings_off_edges.py -q
"""

import datetime
import functools
import math

import pytest

SEEDS = [7, 2147483659, 3141592653, 2718281829, 3900000001]
MARGIN = 0.03


@functools.lru_cache(maxsize=1)
def ship_days(seed):
    import tpch_gen
    from refutil import days
    t = tpch_gen.gen_tables(1, seed, {"lineitem": ["l_shipdate"]})
    return days(t["lineitem"], "l_shipdate")


def rows_in_month(seed, b):
    from refutil import days
    lo = datetime.date(b["year"], b["month"], 1)
    hi = datetime.date(b["year"] + b["month"] // 12, b["month"] % 12 + 1, 1)
    ship = ship_days(seed)
    return int(((ship >= days(lo)) & (ship < days(hi))).sum())


def edge_distance(n):
    """The share by which ``n`` lies from the nearest power of two."""
    lo = 2 ** math.floor(math.log2(n))
    return min((n - lo) / lo, (2 * lo - n) / (2 * lo))


def cell_bindings():
    from run import load_json
    return load_json("traffic", "q14_stream.json")["bindings"]["q14"]


@pytest.mark.parametrize("seed", SEEDS)
def test_q14_bindings_keep_off_every_power_of_two(seed):
    for b in cell_bindings():
        n = rows_in_month(seed, b)
        assert edge_distance(n) >= MARGIN, (seed, b, n)


@pytest.mark.parametrize("seed", SEEDS)
def test_february_1995_fails_the_same_rule(seed):
    """The control: the binding the cell had sits within 1 % of 65 536."""
    n = rows_in_month(seed, {"year": 1995, "month": 2})
    assert edge_distance(n) < MARGIN, (seed, n)


def test_edge_distance():
    assert edge_distance(65536) == 0
    assert edge_distance(65536 * 1.03) == pytest.approx(0.03)
    assert edge_distance(131072 * 0.97) == pytest.approx(0.03)
    assert edge_distance(98304) == pytest.approx(0.25)
