"""TPC-H Q1 (2.4.1): plain integer arithmetic over the generated
lineitem columns, grouped by the two flag columns' dictionary codes.
Sums are Python ints (no overflow); a decimal average rounds half up,
as the engine's decimal(12,2) average does."""

import collections

import numpy as np

from benchmarks.harness.reference import days

KIND = "columns"
TABLE = "lineitem"
COLUMNS = ("l_returnflag", "l_linestatus", "l_quantity",
           "l_extendedprice", "l_discount", "l_tax", "l_shipdate")
_CODES = 1 << 16   # more than either flag dictionary holds


def start(params, control=False):
    cut = days("1998-12-01") - int(params["delta"])
    # per group: qty, base, disc_price, charge, discount, count
    return {"cut": cut, "control": control,
            "acc": collections.defaultdict(lambda: [0] * 6)}


def update(state, cols):
    m = cols["l_shipdate"] <= state["cut"]
    gid = (cols["l_returnflag"] * _CODES + cols["l_linestatus"])[m]
    qty, ext = cols["l_quantity"][m], cols["l_extendedprice"][m]
    disc, tax = cols["l_discount"][m], cols["l_tax"][m]
    disc_price = ext * (100 - disc)
    parts = (qty, ext, disc_price, disc_price * (100 + tax), disc,
             np.ones_like(gid))
    for g in np.unique(gid):
        sel = gid == g
        a = state["acc"][int(g)]
        for i, p in enumerate(parts):
            if state["control"]:
                # the control: the same sums accumulated in float32
                a[i] = float(np.float32(a[i]) + p[sel].astype(
                    np.float32).sum(dtype=np.float32))
            else:
                a[i] += int(p[sel].sum())


def finish(state, dictionaries):
    rows = []
    for g in sorted(state["acc"]):
        qty, base, dp, ch, dsc, cnt = (int(v) for v in state["acc"][g])

        def avg(total):
            return (2 * total + cnt) // (2 * cnt)

        rows.append((
            str(dictionaries["l_returnflag"][g // _CODES]),
            str(dictionaries["l_linestatus"][g % _CODES]),
            qty, base, dp, ch, avg(qty), avg(base), avg(dsc), cnt))
    rows.sort()
    return rows
