"""The planner folds constant subtrees (ISSUE 42, expr/fold.py): an
allow-listed call on non-NULL constants of exact types is the Constant
of its value before the scan starts, computed by the engine's own
implementation; everything else stays the Call it was."""

import datetime

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest
from presto_tpu import types as T
from presto_tpu.connectors.cached import ResidentConnector
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec import plan as P
from presto_tpu.exec.pushdown import extract_ranges
from presto_tpu.expr import functions as F
from presto_tpu.expr import ir
from presto_tpu.expr.eval import evaluate
from presto_tpu.expr.fold import FOLDABLE_CALLS, fold_constants, is_exact
from presto_tpu.page import Block, Page
from presto_tpu.runner import LocalRunner
from presto_tpu.sql import planner as PL
from presto_tpu.sql.parser import parse

SF = 0.01
EPOCH = datetime.date(1970, 1, 1)
DECK = {
    st.key: st
    for cell in ("scan_sf10_resident_solo", "join_sf1_solo")
    for st in manifest.load_cell(cell).every
}


def day(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - EPOCH).days


def date(iso: str) -> ir.Constant:
    return ir.Constant(day(iso), T.DATE)


def months(n: int) -> ir.Constant:
    return ir.Constant(n, T.INTERVAL_YEAR_MONTH)


def days(n: int) -> ir.Constant:
    return ir.Constant(n * 86_400_000_000, T.INTERVAL_DAY_TIME)


def big(v) -> ir.Constant:
    return ir.Constant(v, T.BIGINT)


def dec(unscaled: int, p: int, s: int) -> ir.Constant:
    return ir.Constant(unscaled, T.DecimalType(p, s))


def ts(iso: str) -> ir.Constant:
    dt = datetime.datetime.fromisoformat(iso)
    us = (dt - datetime.datetime(1970, 1, 1)) // datetime.timedelta(
        microseconds=1)
    return ir.Constant(us, T.TIMESTAMP)


@pytest.fixture(scope="module")
def tpch():
    return TpchConnector(SF)


@pytest.fixture(scope="module")
def catalogs(tpch):
    return {"tpch": tpch, "tpch_sf1": tpch}


def plan_of(catalogs, st):
    """(plan as the planner hands it out, Call nodes folded)."""
    planner = PL.Planner(catalogs, st.catalog)
    return planner.plan_statement(parse(st.sql)), planner.constants_folded


def filters_of(node, table):
    """Predicates of the Filter nodes right above a scan of ``table``."""
    out = []
    if isinstance(node, P.Filter) and isinstance(
            node.source, P.TableScan) and node.source.table == table:
        out.append(node.predicate)
    for c in node.children():
        out.extend(filters_of(c, table))
    return out


def conjunct(pred, name, channel_type=T.DateType):
    """The ``name`` comparison of a date column among a filter's
    conjuncts."""
    cs = pred.args if isinstance(pred, ir.SpecialForm) else (pred,)
    (c,) = [c for c in cs if isinstance(c, ir.Call) and c.name == name
            and isinstance(c.args[0].type, channel_type)]
    return c


# ------------------------------------------------------------- the deck

def _upper(iso: str) -> int:
    d = datetime.date.fromisoformat(iso)
    return (d.replace(year=d.year + 1) - EPOCH).days


DECK_DATES = [
    (key, "lineitem", "lt", _upper(DECK[key].params["date"]))
    for key in ("q6_sf10#0", "q6_sf10#1")
] + [
    (key, "lineitem", "le",
     day("1998-12-01") - int(DECK[key].params["delta"]))
    for key in ("q1_sf10#0", "q1_sf10#1")
] + [
    (key, "orders", "lt", _upper(DECK[key].params["date"]))
    for key in ("q5_sf1#0", "q5_sf1#1")
]


def test_the_validation_q6_bound_is_day_9131():
    assert DECK_DATES[0] == ("q6_sf10#0", "lineitem", "lt", 9131)
    assert DECK_DATES[2][3] == 10471  # date '1998-12-01' - 90 days


@pytest.mark.parametrize("key,table,op,want", DECK_DATES,
                         ids=[d[0] for d in DECK_DATES])
def test_deck_date_expression_is_a_date_in_the_plan(
        catalogs, key, table, op, want):
    plan, _n = plan_of(catalogs, DECK[key])
    (pred,) = filters_of(plan, table)
    bound = conjunct(pred, op).args[1]
    assert bound == ir.Constant(want, T.DATE)


@pytest.mark.parametrize("key,want", [
    ("q6_sf10#0", 3), ("q6_sf10#1", 3),     # the date and {discount} -+ 0.01
    ("q1_sf10#0", 1), ("q1_sf10#1", 1),
    ("q5_sf1#0", 1), ("q5_sf1#1", 1),
    ("q3_sf1#0", 0), ("q3_sf1#1", 0),
])
def test_calls_folded_by_statement(catalogs, key, want):
    assert plan_of(catalogs, DECK[key])[1] == want


def test_q6_discount_bounds_keep_the_calls_type(catalogs):
    plan, _n = plan_of(catalogs, DECK["q6_sf10#0"])
    (pred,) = filters_of(plan, "lineitem")
    (between,) = [c for c in pred.args
                  if isinstance(c, ir.SpecialForm) and c.form == ir.BETWEEN]
    lo, hi = between.args[1:]
    raw = ir.call("subtract", dec(6, 2, 2), dec(1, 2, 2))
    assert lo == ir.Constant(5, raw.type) and hi.value == 7
    assert hi.type == ir.call("add", dec(6, 2, 2), dec(1, 2, 2)).type


@pytest.mark.parametrize("key", ["q3_sf1#0", "q3_sf1#1"])
def test_q3_is_the_same_ir_without_the_pass(catalogs, key, monkeypatch):
    with_pass, n = plan_of(catalogs, DECK[key])
    monkeypatch.setattr(PL, "fold_constants", lambda e: (e, 0))
    without, _ = plan_of(catalogs, DECK[key])
    assert n == 0 and with_pass == without


@pytest.mark.parametrize("key", ["q6_sf10#0", "q1_sf10#0", "q5_sf1#0"])
def test_a_folded_statement_differs_only_by_the_pass(
        catalogs, key, monkeypatch):
    with_pass, _ = plan_of(catalogs, DECK[key])
    monkeypatch.setattr(PL, "fold_constants", lambda e: (e, 0))
    without, n = plan_of(catalogs, DECK[key])
    assert n == 0 and with_pass != without
    assert "add(" in repr(without) or "subtract(" in repr(without)


def test_a_group_probe_does_not_count(catalogs):
    """ExprTranslator._group_probe translates, looks and throws away:
    what it folds is not the statement's."""
    planner = PL.Planner(catalogs, "tpch")
    planner.plan_statement(parse(
        "select l_returnflag, sum(l_quantity) * (1 + 1) from lineitem "
        "where l_shipdate < date '1994-01-31' + interval '1' month "
        "group by l_returnflag"))
    assert planner.constants_folded == 2


# ------------------------------------- folded against row-wise evaluation

SLOTS = 8
AGREE = {
    "q6-date-plus-year": ir.call("add", date("1994-01-01"), months(12)),
    "q1-date-minus-days": ir.call("subtract", date("1998-12-01"), days(90)),
    "month-end-clamp": ir.call("add", date("1994-01-31"), months(1)),
    "month-end-clamp-leap": ir.call("add", date("1996-01-31"), months(1)),
    "leap-day-plus-year": ir.call("add", date("1996-02-29"), months(12)),
    "leap-day-minus-year": ir.call("subtract", date("1996-02-29"),
                                   months(12)),
    "interval-plus-date": ir.call("add", months(13), date("1999-12-31")),
    "before-the-epoch": ir.call("subtract", date("1970-03-31"), months(13)),
    "timestamp-plus-month": ir.call(
        "add", ts("2000-01-31 13:45:10"), months(1)),
    "timestamp-minus-days": ir.call(
        "subtract", ts("2000-03-01 00:00:01"), days(1)),
    "date-minus-date": ir.call("subtract", date("1995-03-15"),
                               date("1994-01-01")),
    "decimal-minus": ir.call("subtract", dec(6, 2, 2), dec(1, 2, 2)),
    "decimal-plus-rescaled": ir.call("add", dec(6, 2, 2), dec(125, 4, 3)),
    "decimal-times": ir.call("multiply", dec(-105, 3, 2), dec(333, 3, 1)),
    "decimal-divide-half-up": ir.call("divide", dec(100, 3, 2), dec(3, 1, 0)),
    "decimal-modulus": ir.call("modulus", dec(-725, 3, 2), dec(2, 1, 0)),
    "bigint-divide-truncates": ir.call("divide", big(-7), big(2)),
    "bigint-modulus-keeps-sign": ir.call("modulus", big(-7), big(3)),
    "bigint-times-wraps": ir.call("multiply", big(1 << 62), big(6)),
    "negate": ir.call("negate", ir.call("add", big(2), big(3))),
    "abs": ir.call("abs", dec(-725, 3, 2)),
    "cast-bigint-decimal": ir.cast(big(24), T.DecimalType(12, 2)),
    "cast-decimal-bigint-rounds": ir.cast(dec(-250, 3, 2), T.BIGINT),
    "cast-bigint-integer": ir.cast(big(24), T.INTEGER),
    "cast-date-timestamp": ir.cast(date("1994-02-28"), T.TIMESTAMP),
    "cast-timestamp-date": ir.cast(ts("1969-12-31 23:59:59"), T.DATE),
    "cast-bigint-boolean": ir.cast(big(0), T.BOOLEAN),
    "year": ir.call("year", date("1996-02-29")),
    "week": ir.call("week", date("1999-01-03")),
    "day-of-year": ir.call("day_of_year", ts("1996-12-31 23:00:00")),
    "hour": ir.call("hour", ts("2000-01-31 13:45:10")),
    "nested": ir.call(
        "add", ir.call("subtract", date("1996-03-31"), months(1)),
        ir.call("add", months(2), months(4))),
}


def _page():
    return Page(
        blocks=(Block(data=jnp.arange(SLOTS, dtype=jnp.int64),
                      type=T.BIGINT, nulls=None, dictionary=None),),
        valid=jnp.ones((SLOTS,), dtype=bool))


@pytest.mark.parametrize("name", sorted(AGREE))
def test_folded_value_is_the_row_wise_value(name):
    """The fold is off by evaluating the raw tree: no switch."""
    raw = AGREE[name]
    folded, n = fold_constants(raw)
    assert isinstance(folded, ir.Constant) and n >= 1
    assert folded.type == raw.type
    assert type(folded.value) is (
        bool if isinstance(raw.type, T.BooleanType) else int)
    rows = evaluate(raw, _page(), jnp)
    assert rows.nulls is None or not bool(np.any(np.asarray(rows.nulls)))
    want = np.broadcast_to(np.asarray(rows.data), (SLOTS,))
    assert want.dtype == np.dtype(raw.type.numpy_dtype)
    assert (want == folded.value).all(), (want, folded)
    again = evaluate(folded, _page(), jnp)
    assert np.asarray(again.data).dtype == want.dtype
    assert fold_constants(folded) == (folded, 0)  # twice is once


@pytest.mark.parametrize("name,iso", [
    ("month-end-clamp", "1994-02-28"),
    ("month-end-clamp-leap", "1996-02-29"),
    ("leap-day-plus-year", "1997-02-28"),
    ("leap-day-minus-year", "1995-02-28"),
    ("interval-plus-date", "2001-01-31"),
    ("before-the-epoch", "1969-02-28"),
])
def test_calendar_cases_are_the_calendars(name, iso):
    assert fold_constants(AGREE[name])[0] == date(iso)


def test_folding_twice_is_folding_once_inside_a_tree():
    ref = ir.InputRef(0, T.DATE)
    raw = ir.and_(
        ir.call("ge", ref, date("1994-01-01")),
        ir.call("lt", ref, AGREE["q6-date-plus-year"]),
        ir.between(ir.InputRef(1, T.DecimalType(12, 2)),
                   AGREE["decimal-minus"],
                   ir.call("add", dec(6, 2, 2), dec(1, 2, 2))))
    once, n = fold_constants(raw)
    assert n == 3 and "add(" not in repr(once)
    twice, m = fold_constants(once)
    assert m == 0 and twice is once


def test_a_tree_with_nothing_to_fold_is_the_same_object():
    raw = ir.and_(ir.call("lt", ir.InputRef(0, T.DATE), date("1995-03-15")),
                  ir.call("eq", big(1), big(1)))
    assert fold_constants(raw) == (raw, 0)
    assert fold_constants(raw)[0] is raw


# --------------------------------------------------- what is left alone

LEFT_ALONE = {
    "double-argument": ir.call("add", ir.Constant(1.5, T.DOUBLE), big(1)),
    "double-result": ir.cast(big(3), T.DOUBLE),
    "real-argument": ir.cast(ir.Constant(1.5, T.REAL), T.BIGINT),
    "varchar-argument": ir.cast(ir.Constant("1994-01-01", T.VARCHAR),
                                T.DATE),
    "varchar-result": ir.cast(big(3), T.VARCHAR),
    "concat": ir.call("concat", ir.Constant("a", T.VARCHAR),
                      ir.Constant("b", T.VARCHAR)),
    "array-argument": ir.Call(
        "cardinality",
        (ir.Constant((1, 2), T.ArrayType(T.BIGINT)),), T.BIGINT),
    "long-decimal": ir.Call(
        "add", (dec(1, 38, 0), dec(1, 38, 0)), T.DecimalType(38, 0)),
    "null-argument": ir.call("add", date("1994-01-01"),
                             ir.Constant(None, T.INTERVAL_YEAR_MONTH)),
    "untyped-null": ir.Call("add", (big(1), ir.null()), T.BIGINT),
    "comparison-off-the-list": ir.call("lt", big(1), big(2)),
    "not-off-the-list": ir.not_(ir.Constant(True, T.BOOLEAN)),
    "try-cast-off-the-list": ir.Call("try_cast", (big(1),), T.INTEGER),
    "random-off-the-list": ir.Call("random", (big(10),), T.BIGINT),
    "now-off-the-list": ir.Call("now", (), T.TIMESTAMP),
    "current-date-off-the-list": ir.Call("current_date", (), T.DATE),
    "evaluation-raises": ir.cast(date("1994-01-01"), T.BIGINT),
    "wrong-arity-raises": ir.Call("add", (big(1),), T.BIGINT),
    "divide-by-zero-is-null": ir.call("divide", big(1), big(0)),
    "decimal-modulus-by-zero-is-null": ir.call(
        "modulus", dec(100, 3, 2), dec(0, 1, 0)),
    "non-constant-argument": ir.call("add", ir.InputRef(0, T.BIGINT),
                                     big(1)),
}


@pytest.mark.parametrize("name", sorted(LEFT_ALONE))
def test_left_alone(name):
    raw = LEFT_ALONE[name]
    out, n = fold_constants(raw)
    assert n == 0 and out is raw and isinstance(out, ir.Call)


TWO = ir.call("add", big(1), big(1))
YES = ir.cast(big(1), T.BOOLEAN)
SPECIAL_FORMS = {
    "and": lambda x=ir.Constant(False, T.BOOLEAN): ir.and_(
        ir.Constant(True, T.BOOLEAN), x),
    "if": lambda x=big(2): ir.if_(ir.Constant(True, T.BOOLEAN), big(1), x),
    "in": lambda x=big(2): ir.in_(big(1), big(1), x),
    "between": lambda x=big(2): ir.between(big(2), big(1), x),
    "coalesce": lambda x=big(2): ir.coalesce(big(1), x),
}


@pytest.mark.parametrize("form", sorted(SPECIAL_FORMS))
def test_special_forms_over_constants_are_not_this_prs(form):
    raw = SPECIAL_FORMS[form]()
    assert fold_constants(raw) == (raw, 0)
    out, n = fold_constants(
        SPECIAL_FORMS[form](YES if form == "and" else TWO))
    assert n == 1 and isinstance(out, ir.SpecialForm)
    assert out == SPECIAL_FORMS[form](
        ir.Constant(True, T.BOOLEAN) if form == "and" else big(2))


def test_a_lambda_body_folds():
    body = ir.call("add", ir.ParamRef(0, T.BIGINT),
                   ir.call("add", big(1), big(2)))
    out, n = fold_constants(ir.Lambda(1, body, T.BIGINT))
    assert n == 1 and out.body.args[1] == big(3)


def test_the_allow_list_is_written_down():
    """Folding goes by an allow-list of operators and date/time field
    functions, every one registered, none whose value is not a
    function of its arguments."""
    registered = set(F.registered_names())
    assert FOLDABLE_CALLS <= registered
    assert FOLDABLE_CALLS == {
        "add", "subtract", "multiply", "divide", "modulus", "negate",
        "abs", "cast", "year", "month", "day", "quarter", "week",
        "day_of_week", "day_of_year", "hour", "minute", "second",
        "millisecond"}
    volatile = {n for n in registered if n in (
        "random", "rand", "now", "uuid", "shuffle")
        or n.startswith("current_")}
    assert not FOLDABLE_CALLS & volatile


@pytest.mark.parametrize("t,exact", [
    (T.BIGINT, True), (T.INTEGER, True), (T.SMALLINT, True),
    (T.TINYINT, True), (T.BOOLEAN, True), (T.DATE, True),
    (T.TIMESTAMP, True), (T.INTERVAL_DAY_TIME, True),
    (T.INTERVAL_YEAR_MONTH, True), (T.DecimalType(18, 2), True),
    (T.DecimalType(19, 2), False), (T.DOUBLE, False), (T.REAL, False),
    (T.VARCHAR, False), (T.UNKNOWN, False),
    (T.ArrayType(T.BIGINT), False),
], ids=str)
def test_exact_types(t, exact):
    assert is_exact(t) is exact


def test_no_knob_turns_the_fold_off():
    from presto_tpu.config import ETC_SESSION_KEYS
    from presto_tpu.session import SYSTEM_SESSION_PROPERTIES

    names = list(SYSTEM_SESSION_PROPERTIES) + list(ETC_SESSION_KEYS)
    assert len(names) > 50
    assert not [n for n in names if "fold" in n.lower()]


# ------------------------------------------------- the statements' answers

@pytest.mark.parametrize("sql", [
    "select count(*), sum(l_quantity) from lineitem "
    "where l_shipdate < date '1994-01-31' + interval '1' month",
    "select count(*) from lineitem where l_shipdate >= date '1996-02-29' "
    "- interval '1' year and l_discount between 0.06 - 0.01 and 0.06 + 0.01",
    "select l_returnflag, sum(l_quantity * (1 + 1)) from lineitem "
    "where year(l_shipdate) = year(date '1994-06-01') + 1 "
    "group by l_returnflag order by 1",
    "select sum(l_extendedprice * l_discount) / (3 - 1) from lineitem "
    "where l_quantity < 20 + 4 having sum(l_quantity) > 10 * 10",
], ids=["month-end", "leap-and-decimals", "year-and-projection",
        "aggregate-and-having"])
def test_a_statement_answers_the_same_folded_and_not(tpch, sql, monkeypatch):
    runner = LocalRunner({"tpch": tpch}, default_catalog="tpch")
    folded = runner.execute(sql).rows
    assert runner.executor.plan_constants_folded >= 1
    monkeypatch.setattr(PL, "fold_constants", lambda e: (e, 0))
    raw = LocalRunner({"tpch": tpch}, default_catalog="tpch")
    assert raw.execute(sql).rows == folded
    assert raw.executor.plan_constants_folded == 0


# ----------------------------------------------------------- the pushdown

def _scan(node, table):
    if isinstance(node, P.TableScan) and node.table == table:
        return node
    for c in node.children():
        got = _scan(c, table)
        if got is not None:
            return got
    return None


def test_extract_ranges_has_both_bounds_of_q6s_shipdate(
        catalogs, monkeypatch):
    st = DECK["q6_sf10#0"]
    plan, _ = plan_of(catalogs, st)
    (pred,) = filters_of(plan, "lineitem")
    ch = conjunct(pred, "lt").args[0].channel
    after = extract_ranges(pred, 16)
    assert after[ch] == (day("1994-01-01"), 9130)
    disc = [c for c in after if c != ch]
    assert [after[c] for c in disc] == [(5, 7)]     # 0.05 .. 0.07
    monkeypatch.setattr(PL, "fold_constants", lambda e: (e, 0))
    plan, _ = plan_of(catalogs, st)
    (pred,) = filters_of(plan, "lineitem")
    assert extract_ranges(pred, 16) == {ch: (day("1994-01-01"), None)}


@pytest.mark.parametrize("key,table", [
    ("q6_sf10#0", "lineitem"), ("q1_sf10#0", "lineitem"),
    ("q5_sf1#0", "orders")])
@pytest.mark.parametrize("catalog", ["tpch", "resident"])
def test_the_new_bounds_prune_no_split(tpch, catalog, key, table):
    """The generator inverts key columns only and the resident store
    prunes through its inner connector: the bounds are hints that drop
    nothing, so a stored Q6 reads the splits it read."""
    conn = tpch if catalog == "tpch" else ResidentConnector(
        TpchConnector(SF), tables=["lineitem"])
    runner = LocalRunner({"tpch": conn, "tpch_sf1": conn},
                         default_catalog="tpch")
    scan = _scan(runner.plan(DECK[key].sql), table)
    bounds = {c: (lo, hi) for c, lo, hi in scan.constraint}
    column = "l_shipdate" if table == "lineitem" else "o_orderdate"
    assert bounds[column][1] is not None
    splits = conn.splits(table, 1024)
    assert len(splits) > 10
    assert conn.prune_splits(table, splits, scan.constraint) == splits
