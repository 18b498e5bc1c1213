"""TPC-H Q12, shipping modes and order priority: join, two counts."""

import datetime

import numpy as np
import pyarrow as pa

TABLES = {"lineitem": ["l_orderkey", "l_shipmode", "l_shipdate",
                       "l_commitdate", "l_receiptdate"],
          "orders": ["o_orderkey", "o_orderpriority"]}
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
HIGH = ("1-URGENT", "2-HIGH")


def draw_bindings(rng, k):
    """qgen's ranges (cl. 2.4.12.3): two different ship modes, DATE the
    first of January of a year in 1993..1997."""
    out = []
    for _ in range(k):
        m1, m2 = rng.choice(len(MODES), 2, replace=False)
        out.append({"shipmode1": MODES[m1], "shipmode2": MODES[m2],
                    "year": int(rng.integers(1993, 1998))})
    return out


def build(session, tables, b):
    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.sql.column import col
    lo = datetime.date(b["year"], 1, 1)
    hi = datetime.date(b["year"] + 1, 1, 1)
    li = (session.createDataFrame(tables["lineitem"])
          .select(*TABLES["lineitem"])
          .filter(col("l_shipmode").isin(b["shipmode1"], b["shipmode2"])
                  & (col("l_receiptdate") >= lo)
                  & (col("l_receiptdate") < hi)
                  & (col("l_commitdate") < col("l_receiptdate"))
                  & (col("l_shipdate") < col("l_commitdate"))))
    orders = (session.createDataFrame(tables["orders"])
              .select(*TABLES["orders"]))
    high = F.when(col("o_orderpriority").isin(*HIGH), 1).otherwise(0)
    return (li.join(orders, col("l_orderkey") == col("o_orderkey"))
            .groupBy("l_shipmode")
            .agg(F.sum(high).alias("high_line_count"),
                 F.sum(1 - high).alias("low_line_count"))
            .orderBy("l_shipmode"))


def reference(tables, b, dtype=np.float64):
    """Integer counts only: ``dtype`` changes nothing here."""
    from refutil import days, lookup, strings
    li, orders = tables["lineitem"], tables["orders"]
    lo = datetime.date(b["year"], 1, 1)
    hi = datetime.date(b["year"] + 1, 1, 1)
    mode = strings(li, "l_shipmode")
    ship, commit, receipt = (days(li, c) for c in
                             ("l_shipdate", "l_commitdate", "l_receiptdate"))
    keep = (np.isin(mode, [b["shipmode1"], b["shipmode2"]])
            & (receipt >= days(lo)) & (receipt < days(hi))
            & (commit < receipt) & (ship < commit))
    pos = lookup(orders.column("o_orderkey").to_numpy(),
                 li.column("l_orderkey").to_numpy()[keep])
    matched = pos >= 0
    high = np.isin(strings(orders, "o_orderpriority")[pos[matched]], HIGH)
    mode = mode[keep][matched]
    modes = sorted(set(mode.tolist()))
    return pa.table({
        "l_shipmode": pa.array(modes, type=pa.string()),
        "high_line_count": pa.array(
            [int(high[mode == m].sum()) for m in modes], type=pa.int64()),
        "low_line_count": pa.array(
            [int((~high[mode == m]).sum()) for m in modes], type=pa.int64())})


def min_bytes(tables):
    from refutil import column_bytes
    return column_bytes(tables, TABLES)
