"""Plain reference of TPC-H Q18: the quantity of every order summed in
numpy, the orders over the threshold, two pandas merges, the group sum
and the first hundred. `dtype` is the precision of the sums and of
`o_totalprice` as delivered (float32 for the control)."""

import numpy as np
import pandas as pd


def reference(tables, params, dtype=np.float64):
    lkey = tables.column("lineitem", "l_orderkey")
    qty = tables.column("lineitem", "l_quantity").astype(dtype)
    # sum each order in `dtype` itself: bincount would widen a float32
    order = np.argsort(lkey, kind="stable")
    lkey, qty = lkey[order], qty[order]
    if not len(lkey):
        return []
    starts = np.flatnonzero(np.r_[True, lkey[1:] != lkey[:-1]])
    total = np.add.reduceat(qty, starts, dtype=dtype)
    big = total > dtype(params["QUANTITY"])
    li = pd.DataFrame({"l_orderkey": lkey[starts][big],
                       "quantity": total[big]})
    o = pd.DataFrame({k: tables.column("orders", k) for k in (
        "o_orderkey", "o_custkey", "o_orderdate", "o_totalprice")})
    c = pd.DataFrame({k: tables.column("customer", k) for k in (
        "c_custkey", "c_name")})
    # the statement's group key holds o_orderkey, which is unique in
    # orders, and c_custkey, unique in customer: a group is an order, its
    # sum(l_quantity) the order's own
    g = (li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
         .merge(c, left_on="o_custkey", right_on="c_custkey"))
    g["o_totalprice"] = g["o_totalprice"].to_numpy().astype(dtype)
    g = g.sort_values(["o_totalprice", "o_orderdate"],
                      ascending=[False, True], kind="stable").head(100)
    words = tables.words("customer", "c_name")
    # the statement protocol delivers a DATE as days since 1970-01-01
    return [[str(words[int(r.c_name)]), int(r.c_custkey),
             int(r.o_orderkey), int(r.o_orderdate), float(r.o_totalprice),
             float(r.quantity)] for r in g.itertuples()]
