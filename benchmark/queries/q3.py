"""TPC-H Q3, shipping priority: customer, orders and lineitem joined,
one sum an order, the ten largest.

The specification groups by ``l_orderkey``; the plan groups by
``o_orderkey``, which the join makes equal to it, and returns it under
that name (as the builder this file replaces did).

Ship dates are dbgen's, not ``tpch_gen``'s: the generator draws
``l_shipdate`` independently of the order's date, and Q3 is the query
whose sizes hang on the two together (an order placed before DATE with a
line item shipped after it).  ``lineitem`` below replaces the column by
the specification's rule (cl. 4.2.3: the order's date plus 1 .. 121
days) for this query alone; the program and the reference both read that
table, and no other query's data moves."""

import datetime

import numpy as np
import pyarrow as pa

TABLES = {"customer": ["c_custkey", "c_mktsegment"],
          "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                     "o_shippriority"],
          "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                       "l_shipdate"]}
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
LIMIT = 10
SHIP_DAYS = 121       # cl. 4.2.3: l_shipdate = o_orderdate + 1 .. 121
_SHIPPED = "lineitem shipped within 121 days of its order"


def draw_bindings(rng, k):
    """qgen's ranges (cl. 2.4.3.3): SEGMENT one of the five market
    segments, DATE a day of 1995-03-01 .. 1995-03-31."""
    return [{"segment": SEGMENTS[int(rng.integers(0, len(SEGMENTS)))],
             "date": f"1995-03-{int(rng.integers(1, 32)):02d}"}
            for _ in range(k)]


def _date(b):
    return datetime.date.fromisoformat(b["date"])


def lineitem(tables):
    """``tables["lineitem"]`` with ``l_shipdate`` = its order's date + 1
    .. 121 days.  The days are the generator's own seeded draw for the
    column, taken modulo 121 (2 556 days over 121 residues: each within
    5 % of uniform), so the same seed gives the same table.  Made once a
    run and kept beside the tables it was made from: every query of the
    run scans the same Arrow table."""
    if _SHIPPED not in tables:
        from refutil import days, lookup
        li, orders = tables["lineitem"], tables["orders"]
        order_of = lookup(orders.column("o_orderkey").to_numpy(),
                          li.column("l_orderkey").to_numpy())
        ship = (days(orders, "o_orderdate")[order_of] + 1
                + days(li, "l_shipdate") % SHIP_DAYS)
        at = li.schema.get_field_index("l_shipdate")
        tables[_SHIPPED] = li.set_column(
            at, "l_shipdate",
            pa.array(ship.astype(np.int32), type=pa.int32()).cast(
                pa.date32()))
    return tables[_SHIPPED]


def build(session, tables, b):
    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.sql.column import col
    date = _date(b)
    cust = (session.createDataFrame(tables["customer"])
            .select(*TABLES["customer"])
            .filter(col("c_mktsegment") == b["segment"]))
    orders = (session.createDataFrame(tables["orders"])
              .select(*TABLES["orders"])
              .filter(col("o_orderdate") < date))
    li = (session.createDataFrame(lineitem(tables))
          .select(*TABLES["lineitem"])
          .filter(col("l_shipdate") > date))
    revenue = F.sum(col("l_extendedprice") * (1 - col("l_discount")))
    return (cust.join(orders, col("c_custkey") == col("o_custkey"), "inner")
            .join(li, col("o_orderkey") == col("l_orderkey"), "inner")
            .groupBy("o_orderkey", "o_orderdate", "o_shippriority")
            .agg(revenue.alias("revenue"))
            .orderBy(col("revenue").desc(), col("o_orderdate"))
            .limit(LIMIT))


def reference(tables, b, dtype=np.float64):
    from refutil import days, f, lookup, strings
    cust, orders, li = tables["customer"], tables["orders"], lineitem(tables)
    date = days(_date(b))
    seg_keys = cust.column("c_custkey").to_numpy()[
        strings(cust, "c_mktsegment") == b["segment"]]
    o_date = days(orders, "o_orderdate")
    o_keep = ((o_date < date)
              & (lookup(seg_keys, orders.column("o_custkey").to_numpy()) >= 0))
    o_key = orders.column("o_orderkey").to_numpy()[o_keep]
    l_keep = days(li, "l_shipdate") > date
    pos = lookup(o_key, li.column("l_orderkey").to_numpy()[l_keep])
    matched = pos >= 0
    vol = (f(li, "l_extendedprice", dtype)[l_keep][matched]
           * (dtype(1) - f(li, "l_discount", dtype)[l_keep][matched]))
    # one group an order that has a line item left: o_orderkey is unique,
    # so the date and the priority are the order's own
    by_order = np.argsort(pos[matched], kind="stable")
    group, first = np.unique(pos[matched][by_order], return_index=True)
    ends = np.append(first[1:], len(by_order))
    vol = vol[by_order]
    revenue = np.array([np.sum(vol[lo:hi], dtype=dtype)
                        for lo, hi in zip(first, ends)], dtype=dtype)
    g_date = o_date[o_keep][group]
    top = np.lexsort((g_date, -revenue))[:LIMIT]      # stable
    return pa.table({
        "o_orderkey": pa.array(o_key[group][top], type=pa.int64()),
        "o_orderdate": pa.array(g_date[top], type=pa.int32()).cast(
            pa.date32()),
        "o_shippriority": pa.array(
            orders.column("o_shippriority").to_numpy()[o_keep][group][top],
            type=pa.int32()),
        "revenue": pa.array(revenue[top].astype(np.float64),
                            type=pa.float64())})


def min_bytes(tables):
    from refutil import column_bytes
    return column_bytes(tables, TABLES)
