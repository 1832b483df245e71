"""Plain reference of TPC-H Q13: the NOT LIKE as a Python regular
expression over each order's comment, a pandas LEFT merge of every
customer with the orders that survive it, the non-NULL order keys counted
per customer, then the customers per count, in the clause's order. Every
cell is a BIGINT count: `dtype` is taken for the comparison's interface
and changes nothing."""

import re

import numpy as np
import pandas as pd


def _keeps(word1: str, word2: str):
    """`o_comment not like '%WORD1%WORD2%'`: true where the comment does
    not hold WORD1 with WORD2 somewhere after it (no escape character, so
    the words are literal text)."""
    flagged = re.compile(re.escape(word1) + ".*" + re.escape(word2),
                         re.DOTALL)
    return lambda comment: flagged.search(comment) is None


def reference(tables, params, dtype=np.float64):
    keeps = _keeps(params["WORD1"], params["WORD2"])
    words = tables.words("orders", "o_comment")
    codes = tables.column("orders", "o_comment")
    # the comment column is a code a row into its dictionary: judge each
    # distinct comment once, then read every order's verdict by its code
    kept = np.fromiter((keeps(w) for w in words), bool, len(words))[codes]
    o = pd.DataFrame({"o_orderkey": tables.column("orders", "o_orderkey"),
                      "o_custkey": tables.column("orders", "o_custkey")}
                     )[kept]
    c = pd.DataFrame({"c_custkey": tables.column("customer", "c_custkey")})
    joined = c.merge(o, how="left", left_on="c_custkey",
                     right_on="o_custkey")
    # count(o_orderkey) counts the key where it is not NULL: a customer
    # the join leaves alone counts 0
    c_count = joined.groupby("c_custkey")["o_orderkey"].count()
    dist = c_count.value_counts().rename("custdist").rename_axis(
        "c_count").reset_index()
    dist = dist.sort_values(["custdist", "c_count"],
                            ascending=[False, False], kind="stable")
    return [[int(r.c_count), int(r.custdist)] for r in dist.itertuples()]
