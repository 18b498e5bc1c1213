"""TPC-H Q6, forecasting revenue change: scan, filter, one sum."""

import datetime

import numpy as np
import pyarrow as pa

TABLES = {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                       "l_extendedprice"]}


def draw_bindings(rng, k):
    """qgen's ranges (TPC-H cl. 2.4.6.3): DATE the first of January of
    a year in 1993..1997, DISCOUNT in 0.02..0.09, QUANTITY 24 or 25."""
    out = []
    while len(out) < k:
        b = {"year": int(rng.integers(1993, 1998)),
             "discount": round(0.01 * int(rng.integers(2, 10)), 2),
             "quantity": int(rng.integers(24, 26))}
        if b not in out or k > 80:
            out.append(b)
    return out


def _bounds(b):
    return (datetime.date(b["year"], 1, 1), datetime.date(b["year"] + 1, 1, 1),
            round(b["discount"] - 0.01, 2), round(b["discount"] + 0.01, 2))


def build(session, tables, b):
    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.sql.column import col
    lo, hi, dlo, dhi = _bounds(b)
    return (session.createDataFrame(tables["lineitem"])
            .select(*TABLES["lineitem"])
            .filter((col("l_shipdate") >= lo) & (col("l_shipdate") < hi)
                    & (col("l_discount") >= dlo) & (col("l_discount") <= dhi)
                    & (col("l_quantity") < b["quantity"]))
            .agg(F.sum(col("l_extendedprice") * col("l_discount"))
                 .alias("revenue")))


def reference(tables, b, dtype=np.float64):
    from refutil import days, f
    li = tables["lineitem"]
    lo, hi, dlo, dhi = _bounds(b)
    ship = days(li, "l_shipdate")
    disc, qty = f(li, "l_discount", dtype), f(li, "l_quantity", dtype)
    keep = ((ship >= days(lo)) & (ship < days(hi))
            & (disc >= dtype(dlo)) & (disc <= dtype(dhi))
            & (qty < dtype(b["quantity"])))
    rev = np.sum(f(li, "l_extendedprice", dtype)[keep] * disc[keep],
                 dtype=dtype)
    return pa.table({"revenue": pa.array([float(rev)], type=pa.float64())})


def min_bytes(tables):
    from refutil import column_bytes
    return column_bytes(tables, TABLES)
