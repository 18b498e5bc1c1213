"""TPC-H Q18, large volume customer: the orders whose line items sum to
more than QUANTITY, each with its customer, the hundred largest by
price.

The specification groups by ``c_name, c_custkey, o_orderkey,
o_orderdate, o_totalprice`` and sums ``l_quantity`` again over the
joined rows; the plan is the DataFrame API's, as the builder this file
replaces had it: the sub-aggregate over ``lineitem``, its HAVING, a
``left_semi`` join of ``orders`` against what passes, then ``customer``,
then ``lineitem`` a second time.

An order whose sum lay within ``NEAR`` of QUANTITY would be kept by one
side and dropped by the other inside the stated tolerance of a double;
``near_threshold`` counts them (0 on every seed used: ``l_quantity`` is
continuous, and ``tests/test_tpch_q18.py`` holds it)."""

import numpy as np
import pyarrow as pa

TABLES = {"customer": ["c_custkey", "c_name"],
          "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                     "o_totalprice"],
          "lineitem": ["l_orderkey", "l_quantity"]}
LIMIT = 100
NEAR = 1e-6


def draw_bindings(rng, k):
    """qgen's range (cl. 2.4.18.3): QUANTITY a whole number of
    312 .. 315."""
    return [{"quantity": int(rng.integers(312, 316))} for _ in range(k)]


def build(session, tables, b):
    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.sql.column import col
    li = (session.createDataFrame(tables["lineitem"])
          .select(*TABLES["lineitem"]))
    big = (li.groupBy("l_orderkey")
           .agg(F.sum(col("l_quantity")).alias("sum_qty"))
           .filter(col("sum_qty") > b["quantity"])
           .select("l_orderkey"))
    orders = (session.createDataFrame(tables["orders"])
              .select(*TABLES["orders"])
              .join(big, col("o_orderkey") == col("l_orderkey"),
                    "left_semi"))
    cust = (session.createDataFrame(tables["customer"])
            .select(*TABLES["customer"]))
    return (cust.join(orders, col("c_custkey") == col("o_custkey"))
            .join(li, col("o_orderkey") == col("l_orderkey"))
            .groupBy("c_name", "c_custkey", "o_orderkey", "o_orderdate",
                     "o_totalprice")
            .agg(F.sum(col("l_quantity")).alias("sum_qty"))
            .orderBy(col("o_totalprice").desc(), col("o_orderdate"))
            .limit(LIMIT))


def _order_sums(tables, dtype):
    """Every order that has a line item, and the sum of its quantities
    in ``dtype``: sort by key, sum each run."""
    from refutil import f
    li = tables["lineitem"]
    l_key = li.column("l_orderkey").to_numpy()
    by_order = np.argsort(l_key, kind="stable")
    keys, first = np.unique(l_key[by_order], return_index=True)
    qty = f(li, "l_quantity", dtype)[by_order]
    return keys, np.add.reduceat(qty, first, dtype=dtype)


def near_threshold(tables, b) -> int:
    """Orders whose sum lies within ``NEAR`` of QUANTITY."""
    _, sums = _order_sums(tables, np.float64)
    return int((np.abs(sums - b["quantity"]) <= NEAR).sum())


def reference(tables, b, dtype=np.float64):
    from refutil import days, lookup, strings
    cust, orders = tables["customer"], tables["orders"]
    keys, sums = _order_sums(tables, dtype)
    big = sums > dtype(b["quantity"])
    # o_orderkey is unique and every order has a customer: one group an
    # order that passes, its sum the sub-aggregate's over the same rows
    at = lookup(orders.column("o_orderkey").to_numpy(), keys[big])
    at, sum_qty = at[at >= 0], sums[big][at >= 0]
    price = orders.column("o_totalprice").to_numpy()[at]
    date = days(orders, "o_orderdate")[at]
    top = np.lexsort((date, -price))[:LIMIT]          # stable
    custkey = orders.column("o_custkey").to_numpy()[at][top]
    name = strings(cust, "c_name")[
        lookup(cust.column("c_custkey").to_numpy(), custkey)]
    return pa.table({
        "c_name": pa.array(name.tolist(), type=pa.string()),
        "c_custkey": pa.array(custkey, type=pa.int64()),
        "o_orderkey": pa.array(
            orders.column("o_orderkey").to_numpy()[at][top],
            type=pa.int64()),
        "o_orderdate": pa.array(date[top], type=pa.int32()).cast(
            pa.date32()),
        "o_totalprice": pa.array(price[top], type=pa.float64()),
        "sum_qty": pa.array(sum_qty[top].astype(np.float64),
                            type=pa.float64())})


def min_bytes(tables):
    """``lineitem``'s two columns once, though the plan scans them
    twice: what a query has to read."""
    from refutil import column_bytes
    return column_bytes(tables, TABLES)
