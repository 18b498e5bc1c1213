"""TPC-H Q1, pricing summary report: one grouped aggregate, sorted."""

import datetime

import numpy as np
import pyarrow as pa

TABLES = {"lineitem": ["l_returnflag", "l_linestatus", "l_quantity",
                       "l_extendedprice", "l_discount", "l_tax",
                       "l_shipdate"]}


def draw_bindings(rng, k):
    """qgen's range (cl. 2.4.1.3): DELTA in 60..120 days."""
    return [{"delta": int(d)} for d in
            rng.choice(np.arange(60, 121), k, replace=k > 61)]


def _cutoff(b):
    return datetime.date(1998, 12, 1) - datetime.timedelta(days=b["delta"])


def build(session, tables, b):
    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.sql.column import col
    disc = col("l_extendedprice") * (1 - col("l_discount"))
    return (session.createDataFrame(tables["lineitem"])
            .select(*TABLES["lineitem"])
            .filter(col("l_shipdate") <= _cutoff(b))
            .groupBy("l_returnflag", "l_linestatus")
            .agg(F.sum("l_quantity").alias("sum_qty"),
                 F.sum("l_extendedprice").alias("sum_base"),
                 F.sum(disc).alias("sum_disc"),
                 F.sum(disc * (1 + col("l_tax"))).alias("sum_charge"),
                 F.avg("l_quantity").alias("avg_qty"),
                 F.avg("l_extendedprice").alias("avg_price"),
                 F.avg("l_discount").alias("avg_disc"),
                 F.count("*").alias("cnt"))
            .orderBy("l_returnflag", "l_linestatus"))


def reference(tables, b, dtype=np.float64):
    from refutil import days, f, strings
    li = tables["lineitem"]
    keep = days(li, "l_shipdate") <= days(_cutoff(b))
    flag, status = strings(li, "l_returnflag"), strings(li, "l_linestatus")
    qty, price = f(li, "l_quantity", dtype), f(li, "l_extendedprice", dtype)
    disc, tax = f(li, "l_discount", dtype), f(li, "l_tax", dtype)
    one = dtype(1)
    rows = []
    for fl in np.unique(flag):
        for st in np.unique(status):
            g = keep & (flag == fl) & (status == st)
            n = int(g.sum())
            if not n:
                continue
            q, p, d, t = qty[g], price[g], disc[g], tax[g]
            dp = p * (one - d)
            s = [np.sum(x, dtype=dtype) for x in (q, p, dp, dp * (one + t))]
            avg = [np.sum(x, dtype=dtype) / dtype(n) for x in (q, p, d)]
            rows.append([str(fl), str(st)] + [float(x) for x in s + avg] + [n])
    rows.sort(key=lambda r: (r[0], r[1]))
    names = ["l_returnflag", "l_linestatus", "sum_qty", "sum_base",
             "sum_disc", "sum_charge", "avg_qty", "avg_price", "avg_disc",
             "cnt"]
    types = [pa.string()] * 2 + [pa.float64()] * 7 + [pa.int64()]
    return pa.table({n: pa.array([r[i] for r in rows], type=t)
                     for i, (n, t) in enumerate(zip(names, types))})


def min_bytes(tables):
    from refutil import column_bytes
    return column_bytes(tables, TABLES)
