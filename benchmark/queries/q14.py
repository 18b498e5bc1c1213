"""TPC-H Q14, promotion effect: join to ``part``, ratio of two sums."""

import datetime

import numpy as np
import pyarrow as pa

TABLES = {"lineitem": ["l_partkey", "l_extendedprice", "l_discount",
                       "l_shipdate"],
          "part": ["p_partkey", "p_type"]}


def draw_bindings(rng, k):
    """qgen's range (cl. 2.4.14.3): DATE the first day of a month of
    1993..1997."""
    months = rng.choice(60, k, replace=k > 60)
    return [{"year": 1993 + int(m) // 12, "month": int(m) % 12 + 1}
            for m in months]


def _range(b):
    lo = datetime.date(b["year"], b["month"], 1)
    hi = (datetime.date(b["year"] + 1, 1, 1) if b["month"] == 12
          else datetime.date(b["year"], b["month"] + 1, 1))
    return lo, hi


def build(session, tables, b):
    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.sql.column import col
    lo, hi = _range(b)
    li = (session.createDataFrame(tables["lineitem"])
          .select(*TABLES["lineitem"])
          .filter((col("l_shipdate") >= lo) & (col("l_shipdate") < hi)))
    part = session.createDataFrame(tables["part"]).select(*TABLES["part"])
    vol = col("l_extendedprice") * (1 - col("l_discount"))
    promo = F.when(col("p_type").like("PROMO%"), vol).otherwise(0.0)
    return (li.join(part, col("l_partkey") == col("p_partkey"))
            .agg(F.sum(promo).alias("promo"), F.sum(vol).alias("total"))
            .select((100.0 * col("promo") / col("total"))
                    .alias("promo_revenue")))


def reference(tables, b, dtype=np.float64):
    from refutil import days, f, lookup, strings
    li, part = tables["lineitem"], tables["part"]
    lo, hi = _range(b)
    ship = days(li, "l_shipdate")
    keep = (ship >= days(lo)) & (ship < days(hi))
    pos = lookup(part.column("p_partkey").to_numpy(),
                 li.column("l_partkey").to_numpy()[keep])
    matched = pos >= 0
    is_promo = np.char.startswith(strings(part, "p_type"),
                                  "PROMO")[pos[matched]]
    vol = (f(li, "l_extendedprice", dtype)[keep][matched]
           * (dtype(1) - f(li, "l_discount", dtype)[keep][matched]))
    promo = np.sum(np.where(is_promo, vol, dtype(0)), dtype=dtype)
    total = np.sum(vol, dtype=dtype)
    return pa.table({"promo_revenue": pa.array(
        [float(dtype(100) * promo / total)], type=pa.float64())})


def min_bytes(tables):
    from refutil import column_bytes
    return column_bytes(tables, TABLES)
