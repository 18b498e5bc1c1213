"""TPC-H-shaped tables from a seed: the benchmark's own generator.

A copy of ``bench.gen_tpch`` (schema, cardinalities and value
distributions of the eight relations; NOT dbgen data), kept here so
that no later PR can change the data a cell runs on.  Same seed, same
tables, value for value, as ``bench.gen_tpch(sf, seed)`` gave when the
copy was taken (``tests/test_gen.py`` holds the two against each
other while ``bench.py`` still exists).

Two things differ from the original, neither in the values:

* a caller names the relations and columns it needs and nothing else is
  built (every run of every cell pays for data generation in set-up);
* strings are built as dictionary take, not as Python lists.

Each relation draws from its own stream ``default_rng([seed, k])`` and
the draws inside a relation keep the original's order, so a column's
values do not depend on which other columns were asked for.
"""

from typing import Dict, Iterable, Optional

import numpy as np
import pyarrow as pa

COLORS = ["almond", "antique", "aquamarine", "azure", "beige", "bisque",
          "black", "blanched", "blue", "blush", "brown", "burlywood",
          "burnished", "chartreuse", "chiffon", "chocolate", "coral",
          "cornflower", "cornsilk", "cream", "cyan", "dark", "deep",
          "dim", "dodger", "drab", "firebrick", "floral", "forest",
          "frosted", "gainsboro", "ghost", "goldenrod", "green", "grey",
          "honeydew", "hot", "indian", "ivory", "khaki", "lace",
          "lavender", "lawn", "lemon", "light", "lime", "linen"]
TYPES1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPES2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPES3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONT1 = ["SM", "MED", "LG", "JUMBO", "WRAP"]
CONT2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
WORDS = ["slyly", "quick", "pending", "final", "ironic", "express",
         "bold", "regular", "even", "special", "silent", "furious",
         "careful", "requests", "deposits", "accounts", "packages",
         "Complaints", "Customer", "theodolites", "pinto", "waters"]

# first and last day (exclusive) of the order and ship dates, as days
# since 1970-01-01: 1992-01-01 .. 1998-12-31
DATE_LO, DATE_HI = 8036, 10_592

RELATIONS = ("lineitem", "orders", "customer", "nation", "region",
             "supplier", "part", "partsupp")


def cardinalities(sf: float) -> Dict[str, int]:
    n_part = max(int(200_000 * sf), 16)
    return {"lineitem": int(6_000_000 * sf), "orders": int(1_500_000 * sf),
            "customer": max(int(150_000 * sf), 10), "nation": 25,
            "region": 5, "supplier": max(int(10_000 * sf), 8),
            "part": n_part, "partsupp": 4 * n_part}


def _take(words, idx) -> pa.Array:
    """``[words[i] for i in idx]`` as an Arrow string array."""
    return pa.DictionaryArray.from_arrays(
        pa.array(np.asarray(idx, dtype=np.int32)),
        pa.array(list(words), type=pa.string())).cast(pa.string())


def _choice(rng, words, n) -> np.ndarray:
    """The indices ``rng.choice(words, n)`` would pick (same stream)."""
    return rng.integers(0, len(words), n)


def _date(days) -> pa.Array:
    return pa.array(np.asarray(days, dtype=np.int32),
                    type=pa.int32()).cast(pa.date32())


def _fmt(prefix: str, ints, width: int) -> pa.Array:
    """``[f"{prefix}{i:0{width}d}" for i in ints]``."""
    digits = np.char.zfill(np.asarray(ints).astype(str), width)
    return pa.array(np.char.add(prefix, digits).tolist(), type=pa.string())


def _phone(nationkey) -> pa.Array:
    i = np.arange(len(nationkey))
    return pa.array([f"{10 + int(nk)}-{a}-{b}" for nk, a, b in
                     zip(nationkey, i % 900 + 100, i % 9000 + 1000)],
                    type=pa.string())


def _comments(rng, n, special_every=0) -> pa.Array:
    """Three random words a row; every ``special_every``-th row carries
    a 'Customer ... Complaints' / 'special ... requests' marker so that
    LIKE predicates match some rows and not others."""
    w = np.asarray(WORDS)[rng.integers(0, len(WORDS), (n, 3))]
    out = [" ".join(r) for r in w]
    if special_every:
        for i in range(0, n, special_every):
            out[i] = ("Customer " + out[i] + " Complaints"
                      if (i // special_every) % 2 == 0
                      else "special " + out[i] + " requests")
    return pa.array(out, type=pa.string())


class _Columns:
    """Columns of one relation in schema order; a string column is a
    thunk that runs only if the column is asked for."""

    def __init__(self, want: Optional[Iterable[str]]):
        self.want = None if want is None else set(want)
        self.cols = {}

    def wants(self, name: str) -> bool:
        return self.want is None or name in self.want

    def add(self, name: str, value) -> None:
        if self.wants(name):
            self.cols[name] = value() if callable(value) else value

    def table(self) -> pa.Table:
        if self.want is not None and self.want - set(self.cols):
            raise KeyError(f"unknown columns {sorted(self.want - set(self.cols))}")
        return pa.table(self.cols)


def _region(c, n, rng, sf):
    c.add("r_regionkey", np.arange(n["region"]))
    c.add("r_name", pa.array(["AFRICA", "AMERICA", "ASIA", "EUROPE",
                              "MIDDLE EAST"]))


def _nation(c, n, rng, sf):
    c.add("n_nationkey", np.arange(n["nation"]))
    c.add("n_regionkey", rng.integers(0, n["region"], n["nation"]))
    c.add("n_name", pa.array([f"NATION_{i:02d}"
                              for i in range(n["nation"])]))


def _customer(c, n, rng, sf):
    k = n["customer"]
    nationkey = rng.integers(0, n["nation"], k)
    c.add("c_custkey", np.arange(k))
    c.add("c_nationkey", nationkey)
    seg = _choice(rng, SEGMENTS, k)
    c.add("c_mktsegment", lambda: _take(SEGMENTS, seg))
    c.add("c_acctbal", rng.uniform(-999, 9999, k))
    c.add("c_name", lambda: _fmt("Customer#", np.arange(k), 9))
    c.add("c_address", lambda: pa.array(
        [f"Addr {i % 997} Way" for i in range(k)]))
    c.add("c_phone", lambda: _phone(nationkey))
    c.add("c_comment", lambda: _comments(rng, k))


def _orders(c, n, rng, sf):
    k = n["orders"]
    c.add("o_orderkey", np.arange(k))
    c.add("o_custkey", rng.integers(0, n["customer"], k))
    c.add("o_orderdate", _date(rng.integers(DATE_LO, DATE_HI, k)))
    c.add("o_shippriority", rng.integers(0, 2, k).astype(np.int32))
    c.add("o_totalprice", rng.uniform(800, 500_000, k))
    # rng.choice(["F","O","P"], k, p=[.49,.49,.02]): one uniform a row
    # looked up in the cumulative distribution
    cdf = np.cumsum([0.49, 0.49, 0.02])
    cdf /= cdf[-1]
    status = cdf.searchsorted(rng.random(k), side="right")
    c.add("o_orderstatus", lambda: _take(["F", "O", "P"], status))
    prio = _choice(rng, PRIORITIES, k)
    c.add("o_orderpriority", lambda: _take(PRIORITIES, prio))
    c.add("o_clerk", lambda: _fmt("Clerk#", np.arange(k) % 1000, 9))
    c.add("o_comment", lambda: _comments(rng, k, special_every=23))


def _supplier(c, n, rng, sf):
    k = n["supplier"]
    nationkey = rng.integers(0, n["nation"], k)
    c.add("s_suppkey", np.arange(k))
    c.add("s_name", lambda: _fmt("Supplier#", np.arange(k), 9))
    c.add("s_address", lambda: pa.array(
        [f"Dock {i % 463} St" for i in range(k)]))
    c.add("s_nationkey", nationkey)
    c.add("s_phone", lambda: _phone(nationkey))
    c.add("s_acctbal", rng.uniform(-999, 9999, k))
    c.add("s_comment", lambda: _comments(rng, k, special_every=17))


def _part(c, n, rng, sf):
    k = n["part"]
    name_ix = rng.integers(0, len(COLORS), (k, 2))
    c.add("p_partkey", np.arange(k))
    c.add("p_name", lambda: _take(
        [f"{a} {b}" for a in COLORS for b in COLORS],
        name_ix[:, 0] * len(COLORS) + name_ix[:, 1]))
    mfgr = rng.integers(1, 6, k)
    c.add("p_mfgr", lambda: _take(
        [f"Manufacturer#{m}" for m in range(6)], mfgr))
    b1, b2 = rng.integers(1, 6, k), rng.integers(1, 6, k)
    c.add("p_brand", lambda: _take(
        [f"Brand#{m}{j}" for m in range(6) for j in range(6)], b1 * 6 + b2))
    t1 = rng.integers(0, 6, k)
    t2 = rng.integers(0, 5, k)
    t3 = rng.integers(0, 5, k)
    c.add("p_type", lambda: _take(
        [f"{a} {b} {d}" for a in TYPES1 for b in TYPES2 for d in TYPES3],
        (t1 * 5 + t2) * 5 + t3))
    c.add("p_size", rng.integers(1, 51, k).astype(np.int32))
    c1, c2 = rng.integers(0, 5, k), rng.integers(0, 8, k)
    c.add("p_container", lambda: _take(
        [f"{a} {b}" for a in CONT1 for b in CONT2], c1 * 8 + c2))
    c.add("p_retailprice", rng.uniform(900, 2000, k))


def _sstep(n) -> int:
    """partsupp's supplier stride (4 suppliers a part)."""
    return n["supplier"] // 4 + 1


def _partsupp(c, n, rng, sf):
    k = n["part"]
    partkey = np.repeat(np.arange(k), 4)
    c.add("ps_partkey", partkey)
    c.add("ps_suppkey", (partkey + np.tile(np.arange(4), k) * _sstep(n))
          % n["supplier"])
    c.add("ps_availqty", rng.integers(1, 10_000, 4 * k).astype(np.int32))
    c.add("ps_supplycost", rng.uniform(1, 1000, 4 * k))


def _lineitem(c, n, rng, sf):
    k = n["lineitem"]
    partkey = rng.integers(0, n["part"], k)
    # (l_partkey, l_suppkey) follow partsupp's formula, so two-key
    # joins hit real rows, as in dbgen
    suppkey = (partkey + rng.integers(0, 4, k) * _sstep(n)) % n["supplier"]
    ship = rng.integers(DATE_LO, DATE_HI, k).astype(np.int32)
    c.add("l_orderkey", rng.integers(0, n["orders"], k))
    c.add("l_partkey", partkey)
    c.add("l_suppkey", suppkey)
    c.add("l_quantity", rng.uniform(1, 50, k))
    c.add("l_extendedprice", rng.uniform(100, 10_000, k))
    c.add("l_discount", rng.uniform(0.0, 0.11, k).round(2))
    c.add("l_tax", rng.uniform(0.0, 0.08, k).round(2))
    flag = _choice(rng, "ANR", k)
    c.add("l_returnflag", lambda: _take("ANR", flag))
    status = _choice(rng, "OF", k)
    c.add("l_linestatus", lambda: _take("OF", status))
    c.add("l_shipdate", _date(ship))
    c.add("l_commitdate",
          _date(ship + rng.integers(-15, 16, k).astype(np.int32)))
    c.add("l_receiptdate",
          _date(ship + rng.integers(1, 31, k).astype(np.int32)))
    mode = _choice(rng, MODES, k)
    c.add("l_shipmode", lambda: _take(MODES, mode))
    instruct = _choice(rng, INSTRUCT, k)
    c.add("l_shipinstruct", lambda: _take(INSTRUCT, instruct))


# relation -> (stream number of bench.gen_tpch, builder); region draws
# nothing, so nation's keys come first from stream 0 there as here
_BUILDERS = {"region": (0, _region), "nation": (0, _nation),
             "customer": (1, _customer), "orders": (2, _orders),
             "supplier": (3, _supplier), "part": (4, _part),
             "partsupp": (5, _partsupp), "lineitem": (6, _lineitem)}


def gen_tables(sf: float, seed: int,
               need: Optional[Dict[str, Optional[Iterable[str]]]] = None
               ) -> Dict[str, pa.Table]:
    """``need`` maps a relation to the columns wanted (None: all); with
    ``need`` None all eight relations are built whole."""
    if need is None:
        need = {r: None for r in RELATIONS}
    n = cardinalities(sf)
    out = {}
    for rel, want in need.items():
        stream, build = _BUILDERS[rel]
        rng = np.random.default_rng([seed, stream])
        cols = _Columns(want)
        build(cols, n, rng, sf)
        out[rel] = cols.table()
    return out
