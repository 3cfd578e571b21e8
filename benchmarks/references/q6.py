"""TPC-H Q6 (2.4.6): plain integer arithmetic over the generated
lineitem columns. Decimals are unscaled ints at scale 2, so
``discount`` 0.06 is 6 and ``quantity`` 24 is 2400; the sum of
extendedprice * discount is exact at scale 4."""

import datetime

import numpy as np

from benchmarks.harness.reference import days

KIND = "columns"
TABLE = "lineitem"
COLUMNS = ("l_quantity", "l_extendedprice", "l_discount", "l_shipdate")


def start(params, control=False):
    lo = datetime.date.fromisoformat(params["date"])
    hi = lo.replace(year=lo.year + 1)
    disc = round(float(params["discount"]) * 100)
    return {
        "lo": days(lo.isoformat()), "hi": days(hi.isoformat()),
        "disc_lo": disc - 1, "disc_hi": disc + 1,
        "qty": int(params["quantity"]) * 100,
        "control": control, "sum": 0, "rows": 0,
    }


def update(state, cols):
    ship, disc = cols["l_shipdate"], cols["l_discount"]
    m = ((ship >= state["lo"]) & (ship < state["hi"])
         & (disc >= state["disc_lo"]) & (disc <= state["disc_hi"])
         & (cols["l_quantity"] < state["qty"]))
    ext = cols["l_extendedprice"][m]
    if state["control"]:
        # the control: the same sum accumulated in float32
        state["sum"] = float(np.float32(state["sum"]) + (
            ext.astype(np.float32) * disc[m].astype(np.float32)
        ).sum(dtype=np.float32))
    else:
        state["sum"] += int((ext * disc[m]).sum())
    state["rows"] += int(m.sum())


def finish(state, dictionaries):
    if state["rows"] == 0:
        return [(None,)]
    return [(int(state["sum"]),)]
