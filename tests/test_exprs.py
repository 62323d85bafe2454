"""Expression engine tests: device-vs-host parity + oracles.

Modeled on the reference's unit/ expression suites and
SparkQueryCompareTestSuite (SURVEY.md §4): every expression is evaluated via
the jit device path and the numpy host path and must agree exactly.
"""

import math
import struct

import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.host import HostBatch
from spark_rapids_tpu import exprs as E
from spark_rapids_tpu.exprs.base import BoundReference as Ref, lit

from harness import (check_expr, check_exprs, py_hash_bytes, py_hash_int,
                     py_hash_long, to_signed32)


def make_batch(schema, data):
    return HostBatch.from_pydict(schema, data)


INT_BATCH = make_batch(
    [("a", dt.INT32), ("b", dt.INT32)],
    {"a": [1, -2, 3, None, 2147483647, 0],
     "b": [7, 3, None, 5, 1, 0]})

LONG_BATCH = make_batch(
    [("a", dt.INT64), ("b", dt.INT64)],
    {"a": [10, -7, None, 2**62, -2**62, 123456789],
     "b": [3, 2, 4, None, 3, -10]})

FLOAT_BATCH = make_batch(
    [("x", dt.FLOAT64), ("y", dt.FLOAT64)],
    {"x": [1.5, -2.25, float("nan"), None, float("inf"), -0.0],
     "y": [2.0, 0.0, 1.0, 3.0, None, 4.0]})

STR_BATCH = make_batch(
    [("s", dt.STRING), ("t", dt.STRING)],
    {"s": ["hello", "WORLD", "", None, "héllo", "  pad  "],
     "t": ["he", "ld", "x", "y", None, "pad"]})


class TestArithmetic:
    def test_add(self):
        check_expr(E.Add(Ref(0, dt.INT32), Ref(1, dt.INT32)), INT_BATCH,
                   [8, 1, None, None, -2147483648, 0])  # wraps like the JVM

    def test_subtract_multiply(self):
        check_exprs([E.Subtract(Ref(0, dt.INT32), Ref(1, dt.INT32)),
                     E.Multiply(Ref(0, dt.INT32), Ref(1, dt.INT32))],
                    INT_BATCH,
                    [(-6, 7), (-5, -6), (None, None), (None, None),
                     (2147483646, 2147483647), (0, 0)])

    def test_divide_null_on_zero(self):
        check_expr(E.Divide(Ref(0, dt.INT32), Ref(1, dt.INT32)), INT_BATCH,
                   [1 / 7, -2 / 3, None, None, 2147483647.0, None])

    def test_integral_divide_truncates(self):
        check_expr(E.IntegralDivide(Ref(0, dt.INT64), Ref(1, dt.INT64)),
                   LONG_BATCH, [3, -3, None, None, -1537228672809129301,
                                -12345678])

    def test_remainder_java_sign(self):
        check_expr(E.Remainder(Ref(0, dt.INT64), Ref(1, dt.INT64)),
                   LONG_BATCH, [1, -1, None, None,
                                -(2**62) - (-1537228672809129301) * 3,
                                123456789 % -10 - -10])

    def test_pmod_nonnegative(self):
        b = make_batch([("a", dt.INT32), ("b", dt.INT32)],
                       {"a": [7, -7, 7, -7], "b": [3, 3, -3, -3]})
        check_expr(E.Pmod(Ref(0, dt.INT32), Ref(1, dt.INT32)), b,
                   [1, 2, -2, -1])

    def test_unary(self):
        check_exprs([E.UnaryMinus(Ref(0, dt.INT32)), E.Abs(Ref(0, dt.INT32))],
                    INT_BATCH,
                    [(-1, 1), (2, 2), (-3, 3), (None, None),
                     (-2147483647, 2147483647), (0, 0)])

    def test_least_greatest_skip_nulls(self):
        check_exprs([E.Least(Ref(0, dt.INT32), Ref(1, dt.INT32)),
                     E.Greatest(Ref(0, dt.INT32), Ref(1, dt.INT32))],
                    INT_BATCH,
                    [(1, 7), (-2, 3), (3, 3), (5, 5), (1, 2147483647), (0, 0)])

    def test_bitwise(self):
        check_exprs([E.BitwiseAnd(Ref(0, dt.INT32), Ref(1, dt.INT32)),
                     E.BitwiseOr(Ref(0, dt.INT32), Ref(1, dt.INT32)),
                     E.BitwiseXor(Ref(0, dt.INT32), Ref(1, dt.INT32)),
                     E.BitwiseNot(Ref(0, dt.INT32))],
                    INT_BATCH,
                    [(1 & 7, 1 | 7, 1 ^ 7, ~1),
                     (-2 & 3, -2 | 3, -2 ^ 3, 1),
                     (None, None, None, -4),
                     (None, None, None, None),
                     (1, 2147483647, 2147483646, -2147483648),
                     (0, 0, 0, -1)])

    def test_shifts(self):
        b = make_batch([("a", dt.INT32), ("n", dt.INT32)],
                       {"a": [1, -8, 256, 1], "n": [3, 1, 33, 0]})
        check_exprs([E.ShiftLeft(Ref(0, dt.INT32), Ref(1, dt.INT32)),
                     E.ShiftRight(Ref(0, dt.INT32), Ref(1, dt.INT32)),
                     E.ShiftRightUnsigned(Ref(0, dt.INT32), Ref(1, dt.INT32))],
                    b,
                    [(8, 0, 0), (-16, -4, 2147483644),
                     (512, 128, 128), (1, 1, 1)])  # shift masked to 5 bits


class TestPredicates:
    def test_comparisons_int(self):
        check_exprs([E.EqualTo(Ref(0, dt.INT32), Ref(1, dt.INT32)),
                     E.LessThan(Ref(0, dt.INT32), Ref(1, dt.INT32)),
                     E.GreaterThanOrEqual(Ref(0, dt.INT32), Ref(1, dt.INT32))],
                    INT_BATCH,
                    [(False, True, False), (False, True, False),
                     (None, None, None), (None, None, None),
                     (False, False, True), (True, False, True)])

    def test_nan_semantics(self):
        # Spark: NaN == NaN is true; NaN > everything.
        b = make_batch([("x", dt.FLOAT64), ("y", dt.FLOAT64)],
                       {"x": [float("nan"), float("nan"), 1.0, float("inf")],
                        "y": [float("nan"), 1.0, float("nan"), float("nan")]})
        check_exprs([E.EqualTo(Ref(0, dt.FLOAT64), Ref(1, dt.FLOAT64)),
                     E.GreaterThan(Ref(0, dt.FLOAT64), Ref(1, dt.FLOAT64)),
                     E.LessThan(Ref(0, dt.FLOAT64), Ref(1, dt.FLOAT64))],
                    b,
                    [(True, False, False), (False, True, False),
                     (False, False, True), (False, False, True)])

    def test_string_compare(self):
        b = make_batch([("s", dt.STRING), ("t", dt.STRING)],
                       {"s": ["abc", "abc", "ab", "b", "", None],
                        "t": ["abc", "abd", "abc", "ab", "a", "x"]})
        check_exprs([E.EqualTo(Ref(0, dt.STRING), Ref(1, dt.STRING)),
                     E.LessThan(Ref(0, dt.STRING), Ref(1, dt.STRING))],
                    b,
                    [(True, False), (False, True), (False, True),
                     (False, False), (False, True), (None, None)])

    def test_kleene_and_or(self):
        b = make_batch([("p", dt.BOOL), ("q", dt.BOOL)],
                       {"p": [True, True, True, False, False, None, None],
                        "q": [True, False, None, False, None, True, None]})
        check_exprs([E.And(Ref(0, dt.BOOL), Ref(1, dt.BOOL)),
                     E.Or(Ref(0, dt.BOOL), Ref(1, dt.BOOL))],
                    b,
                    [(True, True), (False, True), (None, True),
                     (False, False), (False, None), (None, True),
                     (None, None)])

    def test_null_checks(self):
        check_exprs([E.IsNull(Ref(0, dt.INT32)), E.IsNotNull(Ref(0, dt.INT32))],
                    INT_BATCH,
                    [(False, True), (False, True), (False, True),
                     (True, False), (False, True), (False, True)])

    def test_equal_null_safe(self):
        check_expr(E.EqualNullSafe(Ref(0, dt.INT32), Ref(1, dt.INT32)),
                   make_batch([("a", dt.INT32), ("b", dt.INT32)],
                              {"a": [1, None, None, 2],
                               "b": [1, None, 3, 4]}),
                   [True, True, False, False])

    def test_in_set(self):
        check_expr(E.InSet(Ref(0, dt.INT32), [1, 3, None]), INT_BATCH,
                   [True, None, True, None, None, None])
        check_expr(E.InSet(Ref(0, dt.STRING), ["hello", "héllo"]), STR_BATCH,
                   [True, False, False, None, True, False])

    def test_isnan(self):
        check_expr(E.IsNan(Ref(0, dt.FLOAT64)), FLOAT_BATCH,
                   [False, False, True, None, False, False])


class TestMath:
    def test_unary_math(self):
        b = make_batch([("x", dt.FLOAT64)],
                       {"x": [4.0, 0.25, None, 1.0]})
        check_exprs([E.Sqrt(Ref(0, dt.FLOAT64)), E.Exp(Ref(0, dt.FLOAT64)),
                     E.Sin(Ref(0, dt.FLOAT64))],
                    b,
                    [(2.0, math.exp(4.0), math.sin(4.0)),
                     (0.5, math.exp(0.25), math.sin(0.25)),
                     (None, None, None),
                     (1.0, math.e, math.sin(1.0))], approx_float=True)

    def test_log_null_domain(self):
        b = make_batch([("x", dt.FLOAT64)], {"x": [math.e, 0.0, -1.0, None]})
        check_expr(E.Log(Ref(0, dt.FLOAT64)), b, [1.0, None, None, None],
                   approx_float=True)

    def test_floor_ceil_long(self):
        b = make_batch([("x", dt.FLOAT64)], {"x": [1.5, -1.5, 2.0, None]})
        check_exprs([E.Floor(Ref(0, dt.FLOAT64)), E.Ceil(Ref(0, dt.FLOAT64))],
                    b, [(1, 2), (-2, -1), (2, 2), (None, None)])

    def test_round_half_up(self):
        b = make_batch([("x", dt.FLOAT64)],
                       {"x": [2.5, -2.5, 1.25, 1.35, None]})
        check_expr(E.Round(Ref(0, dt.FLOAT64), lit(0)), b,
                   [3.0, -3.0, 1.0, 1.0, None], approx_float=True)
        check_expr(E.Round(Ref(0, dt.FLOAT64), lit(1)), b,
                   [2.5, -2.5, 1.3, 1.4, None], approx_float=True)

    def test_pow(self):
        check_expr(E.Pow(lit(2.0), lit(10.0)), INT_BATCH,
                   [1024.0] * 6, approx_float=True)

    def test_inverse_hyperbolics_datagen(self):
        """asinh/acosh/atanh dual-engine parity over adversarial doubles
        (NaN/±inf/±0/huge), with pandas-style numpy oracles."""
        from data_gen import DoubleGen, unary_op_batch
        b = unary_op_batch(DoubleGen(), n=96, seed=11)
        for cls in (E.Asinh, E.Acosh, E.Atanh):
            check_expr(cls(Ref(0, dt.FLOAT64)), b, approx_float=True)

    def test_acosh_atanh_domains(self):
        b = make_batch([("x", dt.FLOAT64)],
                       {"x": [1.0, 0.5, -2.0, None, 2.0]})
        got = check_expr(E.Acosh(Ref(0, dt.FLOAT64)), b,
                         approx_float=True)
        assert got[0] == 0.0 and math.isnan(got[1]) \
            and math.isnan(got[2]) and got[3] is None
        b = make_batch([("x", dt.FLOAT64)],
                       {"x": [0.5, 1.0, -1.0, 2.0, None]})
        got = check_expr(E.Atanh(Ref(0, dt.FLOAT64)), b,
                         approx_float=True)
        assert abs(got[0] - math.atanh(0.5)) < 1e-12
        assert got[1] == math.inf and got[2] == -math.inf
        assert math.isnan(got[3]) and got[4] is None

    def test_logarithm_arbitrary_base(self):
        """log(base, x): NULL outside the domain (base <= 0, base == 1,
        x <= 0), exact ratios inside it; fuzzed dual-engine parity."""
        b = make_batch([("x", dt.FLOAT64)],
                       {"x": [8.0, 0.5, -1.0, 0.0, None]})
        check_expr(E.Logarithm(lit(2.0), Ref(0, dt.FLOAT64)), b,
                   [3.0, -1.0, None, None, None], approx_float=True)
        b = make_batch([("b", dt.FLOAT64), ("x", dt.FLOAT64)],
                       {"b": [10.0, 1.0, -2.0, 0.5, None],
                        "x": [100.0, 5.0, 5.0, 4.0, 2.0]})
        check_expr(E.Logarithm(Ref(0, dt.FLOAT64), Ref(1, dt.FLOAT64)),
                   b, [2.0, None, None, -2.0, None], approx_float=True)
        from data_gen import DoubleGen, binary_op_batch
        fuzz = binary_op_batch(DoubleGen(), DoubleGen(), n=96, seed=12)
        check_expr(E.Logarithm(Ref(0, dt.FLOAT64), Ref(1, dt.FLOAT64)),
                   fuzz, approx_float=True)


class TestAtLeastNNonNulls:
    def test_basic_and_nan_counts_as_null(self):
        b = make_batch([("a", dt.FLOAT64), ("b", dt.INT64),
                        ("c", dt.STRING)],
                       {"a": [1.0, None, float("nan"), 2.0],
                        "b": [1, None, 3, None],
                        "c": ["x", "y", None, None]})
        exprs = [Ref(0, dt.FLOAT64), Ref(1, dt.INT64), Ref(2, dt.STRING)]
        check_expr(E.AtLeastNNonNulls(2, *exprs), b,
                   [True, False, False, False])
        check_expr(E.AtLeastNNonNulls(1, *exprs), b,
                   [True, True, True, True])
        check_expr(E.AtLeastNNonNulls(0, *exprs), b, [True] * 4)
        check_expr(E.AtLeastNNonNulls(4, *exprs), b, [False] * 4)

    def test_datagen_parity(self):
        from data_gen import (DoubleGen, IntegerGen, StringGen,
                              gen_batch)
        b = gen_batch([("a", DoubleGen()), ("b", IntegerGen()),
                       ("c", StringGen())], 96, seed=13)
        check_expr(E.AtLeastNNonNulls(
            2, Ref(0, dt.FLOAT64), Ref(1, dt.INT32),
            Ref(2, dt.STRING)), b)


class TestConditional:
    def test_if_null_pred_takes_else(self):
        b = make_batch([("p", dt.BOOL), ("a", dt.INT32), ("b", dt.INT32)],
                       {"p": [True, False, None], "a": [1, 2, 3],
                        "b": [10, 20, 30]})
        check_expr(E.If(Ref(0, dt.BOOL), Ref(1, dt.INT32), Ref(2, dt.INT32)),
                   b, [1, 20, 30])

    def test_case_when(self):
        b = make_batch([("x", dt.INT32)], {"x": [1, 5, 15, None]})
        expr = E.CaseWhen(
            [(E.LessThan(Ref(0, dt.INT32), lit(3)), lit(100)),
             (E.LessThan(Ref(0, dt.INT32), lit(10)), lit(200))],
            lit(300))
        check_expr(expr, b, [100, 200, 300, 300])

    def test_case_when_no_else(self):
        b = make_batch([("x", dt.INT32)], {"x": [1, 15]})
        expr = E.CaseWhen([(E.LessThan(Ref(0, dt.INT32), lit(3)), lit(100))])
        check_expr(expr, b, [100, None])

    def test_coalesce(self):
        b = make_batch([("a", dt.INT32), ("b", dt.INT32)],
                       {"a": [None, 2, None], "b": [1, 5, None]})
        check_expr(E.Coalesce(Ref(0, dt.INT32), Ref(1, dt.INT32), lit(9)),
                   b, [1, 2, 9])

    def test_coalesce_strings(self):
        b = make_batch([("a", dt.STRING), ("b", dt.STRING)],
                       {"a": [None, "xy", None], "b": ["abc", "q", None]})
        check_expr(E.Coalesce(Ref(0, dt.STRING), Ref(1, dt.STRING)),
                   b, ["abc", "xy", None])

    def test_nanvl(self):
        check_expr(E.NaNvl(Ref(0, dt.FLOAT64), Ref(1, dt.FLOAT64)),
                   FLOAT_BATCH, [1.5, -2.25, 1.0, None, float("inf"), -0.0])


class TestCast:
    def test_int_widening_narrowing(self):
        b = make_batch([("x", dt.INT64)],
                       {"x": [1, 300, -129, None, 2**40]})
        check_expr(E.Cast(Ref(0, dt.INT64), dt.INT8), b,
                   [1, 44, 127, None, 0])  # JVM wrap-around

    def test_float_to_int_truncate(self):
        b = make_batch([("x", dt.FLOAT64)],
                       {"x": [1.9, -1.9, float("nan"), 1e20, None]})
        check_expr(E.Cast(Ref(0, dt.FLOAT64), dt.INT64), b,
                   [1, -1, 0, 9223372036854775807, None])

    def test_bool_casts(self):
        b = make_batch([("x", dt.INT32)], {"x": [0, 1, -5, None]})
        check_expr(E.Cast(Ref(0, dt.INT32), dt.BOOL), b,
                   [False, True, True, None])

    def test_int_to_string(self):
        b = make_batch([("x", dt.INT32)], {"x": [0, -42, 2147483647, None]})
        check_expr(E.Cast(Ref(0, dt.INT32), dt.STRING), b,
                   ["0", "-42", "2147483647", None])

    def test_string_to_int_invalid_null(self):
        b = make_batch([("s", dt.STRING)],
                       {"s": ["42", " 7 ", "abc", "", None, "99999999999"]})
        check_expr(E.Cast(Ref(0, dt.STRING), dt.INT32), b,
                   [42, 7, None, None, None, None])

    def test_string_to_double(self):
        b = make_batch([("s", dt.STRING)],
                       {"s": ["1.5", "NaN", "-Infinity", "x", None]})
        out = check_expr(E.Cast(Ref(0, dt.STRING), dt.FLOAT64), b)
        assert out[0] == 1.5 and math.isnan(out[1])
        assert out[2] == float("-inf") and out[3] is None and out[4] is None

    def test_timestamp_date_roundtrip(self):
        b = make_batch([("t", dt.TIMESTAMP)],
                       {"t": [0, 86400_000_000 + 3600_000_000,
                              -1, None]})
        check_expr(E.Cast(Ref(0, dt.TIMESTAMP), dt.DATE), b,
                   [0, 1, -1, None])
        b2 = make_batch([("d", dt.DATE)], {"d": [0, 1, -1, None]})
        check_expr(E.Cast(Ref(0, dt.DATE), dt.TIMESTAMP), b2,
                   [0, 86400_000_000, -86400_000_000, None])

    def test_string_to_date(self):
        b = make_batch([("s", dt.STRING)],
                       {"s": ["1970-01-01", "1970-01-02", "1969-12-31",
                              "2020-02-29", "bad", None]})
        check_expr(E.Cast(Ref(0, dt.STRING), dt.DATE), b,
                   [0, 1, -1, 18321, None, None])


class TestDatetime:
    DATES = make_batch(
        [("d", dt.DATE)],
        # 1970-01-01, 2000-02-29, 2020-12-31, 1969-12-31, null
        {"d": [0, 11016, 18627, -1, None]})

    def test_ymd(self):
        check_exprs([E.Year(Ref(0, dt.DATE)), E.Month(Ref(0, dt.DATE)),
                     E.DayOfMonth(Ref(0, dt.DATE))],
                    self.DATES,
                    [(1970, 1, 1), (2000, 2, 29), (2020, 12, 31),
                     (1969, 12, 31), (None, None, None)])

    def test_dow_doy_quarter(self):
        check_exprs([E.DayOfWeek(Ref(0, dt.DATE)),
                     E.DayOfYear(Ref(0, dt.DATE)),
                     E.Quarter(Ref(0, dt.DATE))],
                    self.DATES,
                    # 1970-01-01 was a Thursday -> Spark dayofweek=5
                    [(5, 1, 1), (3, 60, 1), (5, 366, 4), (4, 365, 4),
                     (None, None, None)])

    def test_last_day_add_months(self):
        check_expr(E.LastDay(Ref(0, dt.DATE)), self.DATES,
                   [30, 11016, 18627, 30 - 31, None])
        b = make_batch([("d", dt.DATE), ("n", dt.INT32)],
                       {"d": [0, 11016], "n": [1, 12]})
        # 1970-01-01 +1mo = 1970-02-01 (31); 2000-02-29 +12mo = 2001-02-28
        check_expr(E.AddMonths(Ref(0, dt.DATE), Ref(1, dt.INT32)), b,
                   [31, 11016 + 365])

    def test_time_parts(self):
        b = make_batch([("t", dt.TIMESTAMP)],
                       {"t": [3600_000_000 * 5 + 60_000_000 * 7 + 9_000_000,
                              -1_000_000, None]})
        check_exprs([E.Hour(Ref(0, dt.TIMESTAMP)),
                     E.Minute(Ref(0, dt.TIMESTAMP)),
                     E.Second(Ref(0, dt.TIMESTAMP))],
                    b, [(5, 7, 9), (23, 59, 59), (None, None, None)])

    def test_date_arith(self):
        b = make_batch([("d", dt.DATE), ("n", dt.INT32)],
                       {"d": [100, 0, None], "n": [5, -3, 1]})
        check_exprs([E.DateAdd(Ref(0, dt.DATE), Ref(1, dt.INT32)),
                     E.DateSub(Ref(0, dt.DATE), Ref(1, dt.INT32))],
                    b, [(105, 95), (-3, 3), (None, None)])


class TestStrings:
    def test_upper_lower(self):
        check_exprs([E.Upper(Ref(0, dt.STRING)), E.Lower(Ref(0, dt.STRING))],
                    STR_BATCH,
                    [("HELLO", "hello"), ("WORLD", "world"), ("", ""),
                     (None, None), ("HéLLO", "héllo"),
                     ("  PAD  ", "  pad  ")])

    def test_length_chars(self):
        check_expr(E.Length(Ref(0, dt.STRING)), STR_BATCH,
                   [5, 5, 0, None, 5, 7])  # héllo = 5 chars, 6 bytes

    def test_substring(self):
        b = make_batch([("s", dt.STRING)],
                       {"s": ["hello", "héllo", "ab", None]})
        check_expr(E.Substring(Ref(0, dt.STRING), lit(2), lit(3)), b,
                   ["ell", "éll", "b", None])
        # Spark: start = len + pos; when that is < 0 the requested length is
        # consumed from the virtual negative start ('ab',-3,2 -> 'a').
        check_expr(E.Substring(Ref(0, dt.STRING), lit(-3), lit(2)), b,
                   ["ll", "ll", "a", None])
        check_expr(E.Substring(Ref(0, dt.STRING), lit(0), lit(2)), b,
                   ["he", "hé", "ab", None])

    def test_contains_starts_ends(self):
        check_exprs([E.Contains(Ref(0, dt.STRING), lit("ll")),
                     E.StartsWith(Ref(0, dt.STRING), lit("he")),
                     E.EndsWith(Ref(0, dt.STRING), lit("lo"))],
                    STR_BATCH,
                    [(True, True, True), (False, False, False),
                     (False, False, False), (None, None, None),
                     (True, False, True), (False, False, False)])

    def test_locate(self):
        b = make_batch([("s", dt.STRING)],
                       {"s": ["hello", "lol", "xyz", None]})
        check_expr(E.StringLocate(lit("l"), Ref(0, dt.STRING), lit(1)), b,
                   [3, 1, 0, None])
        check_expr(E.StringLocate(lit("l"), Ref(0, dt.STRING), lit(4)), b,
                   [4, 0, 0, None])

    def test_concat(self):
        check_expr(E.ConcatStrings(Ref(0, dt.STRING), lit("_"),
                                   Ref(1, dt.STRING)),
                   STR_BATCH,
                   ["hello_he", "WORLD_ld", "_x", None, None, "  pad  _pad"])

    def test_trim(self):
        b = make_batch([("s", dt.STRING)],
                       {"s": ["  hi  ", "hi", "   ", "", None]})
        check_exprs([E.StringTrim(Ref(0, dt.STRING)),
                     E.StringTrimLeft(Ref(0, dt.STRING)),
                     E.StringTrimRight(Ref(0, dt.STRING))],
                    b,
                    [("hi", "hi  ", "  hi"), ("hi", "hi", "hi"),
                     ("", "", ""), ("", "", ""), (None, None, None)])

    def test_replace(self):
        b = make_batch([("s", dt.STRING)],
                       {"s": ["banana", "abc", None]})
        check_expr(E.StringReplace(Ref(0, dt.STRING), "an", "AN"), b,
                   ["bANANa", "abc", None])

    def test_regexp_replace(self):
        b = make_batch([("s", dt.STRING)], {"s": ["a1b22c", None]})
        check_expr(E.RegExpReplace(Ref(0, dt.STRING), r"\d+", "#"), b,
                   ["a#b#c", None])

    def test_like(self):
        b = make_batch([("s", dt.STRING)],
                       {"s": ["hello", "help", "yell", "hl", None]})
        check_expr(E.Like(Ref(0, dt.STRING), "hel%"), b,
                   [True, True, False, False, None])
        check_expr(E.Like(Ref(0, dt.STRING), "%ell%"), b,
                   [True, False, True, False, None])
        check_expr(E.Like(Ref(0, dt.STRING), "h_l%"), b,
                   [True, True, False, False, None])
        check_expr(E.Like(Ref(0, dt.STRING), "hello"), b,
                   [True, False, False, False, None])
        check_expr(E.Like(Ref(0, dt.STRING), "h%l%o"), b,
                   [True, False, False, False, None])


class TestMurmur3:
    def test_hash_int_vs_oracle(self):
        vals = [0, 1, -1, 42, 2147483647, -2147483648]
        b = make_batch([("x", dt.INT32)], {"x": vals})
        expected = [to_signed32(py_hash_int(v & 0xFFFFFFFF, 42))
                    for v in vals]
        check_expr(E.Murmur3Hash([Ref(0, dt.INT32)]), b, expected)

    def test_hash_long_vs_oracle(self):
        vals = [0, 1, -1, 2**62, -2**63]
        b = make_batch([("x", dt.INT64)], {"x": vals})
        expected = [to_signed32(py_hash_long(v, 42)) for v in vals]
        check_expr(E.Murmur3Hash([Ref(0, dt.INT64)]), b, expected)

    def test_hash_string_vs_oracle(self):
        vals = ["", "a", "ab", "abc", "abcd", "abcde", "hello world! longer",
                "héllo"]
        b = make_batch([("s", dt.STRING)], {"s": vals})
        expected = [to_signed32(py_hash_bytes(v.encode(), 42)) for v in vals]
        check_expr(E.Murmur3Hash([Ref(0, dt.STRING)]), b, expected)

    def test_hash_double_and_chain(self):
        b = make_batch([("x", dt.FLOAT64), ("y", dt.INT32)],
                       {"x": [1.5, float("nan"), None], "y": [7, 8, 9]})
        exp = []
        for x, y in [(1.5, 7), (float("nan"), 8), (None, 9)]:
            seed = 42
            if x is not None:
                bits = struct.unpack("<q", struct.pack("<d", x))[0] \
                    if not math.isnan(x) else 0x7FF8000000000000
                seed = py_hash_long(bits, seed)
            exp.append(to_signed32(py_hash_int(y, seed)))
        check_expr(E.Murmur3Hash([Ref(0, dt.FLOAT64), Ref(1, dt.INT32)]),
                   b, exp)

    def test_null_passes_seed(self):
        b = make_batch([("x", dt.INT32)], {"x": [None]})
        check_expr(E.Murmur3Hash([Ref(0, dt.INT32)]), b, [42])


class TestReviewRegressions:
    """Regressions for the round-1 code-review findings."""

    def test_substr_int_max_len(self):
        # substr(s, pos) desugars to len = Int.MaxValue; must not wrap.
        b = make_batch([("s", dt.STRING)], {"s": ["hello", "ab", None]})
        check_expr(E.Substring(Ref(0, dt.STRING), lit(2), lit(2147483647)),
                   b, ["ello", "b", None])

    def test_float_to_int_saturates(self):
        b = make_batch([("x", dt.FLOAT64)],
                       {"x": [1e10, -1e10, 1e300, None]})
        # d2i saturation at Int range...
        check_expr(E.Cast(Ref(0, dt.FLOAT64), dt.INT32), b,
                   [2147483647, -2147483648, 2147483647, None])
        # ...then wrap-narrow for byte (Scala x.toInt.toByte).
        check_expr(E.Cast(Ref(0, dt.FLOAT64), dt.INT8), b,
                   [-1, 0, -1, None])

    def test_float_to_timestamp_nan_inf_null(self):
        b = make_batch([("x", dt.FLOAT64)],
                       {"x": [1.5, float("nan"), float("inf"), None]})
        check_expr(E.Cast(Ref(0, dt.FLOAT64), dt.TIMESTAMP), b,
                   [1500000, None, None, None])

    def test_least_nan_is_greatest(self):
        b = make_batch([("x", dt.FLOAT64), ("y", dt.FLOAT64)],
                       {"x": [float("nan"), float("nan"), 1.0,
                              float("inf")],
                        "y": [1.0, float("nan"), 2.0, float("nan")]})
        out = check_expr(E.Least(Ref(0, dt.FLOAT64), Ref(1, dt.FLOAT64)), b)
        assert out[0] == 1.0 and math.isnan(out[1]) and out[2] == 1.0
        assert out[3] == float("inf")
        out = check_expr(E.Greatest(Ref(0, dt.FLOAT64), Ref(1, dt.FLOAT64)),
                         b)
        assert math.isnan(out[0]) and math.isnan(out[1]) and out[2] == 2.0
        assert math.isnan(out[3])

    def test_locate_start_below_one(self):
        b = make_batch([("s", dt.STRING)], {"s": ["hello"]})
        check_expr(E.StringLocate(lit("l"), Ref(0, dt.STRING), lit(0)), b,
                   [0])
        check_expr(E.StringLocate(lit("l"), Ref(0, dt.STRING), lit(-2)), b,
                   [0])

    def test_coalesce_wider_first_string(self):
        # Accumulator narrower than a later (earlier-arg) wider literal.
        b = make_batch([("s", dt.STRING)], {"s": [None, "zz"]})
        check_expr(E.Coalesce(lit("a-very-long-literal-string"),
                              Ref(0, dt.STRING), lit("bb")),
                   b, ["a-very-long-literal-string"] * 2)
        check_expr(E.Coalesce(Ref(0, dt.STRING),
                              lit("a-very-long-literal-string")),
                   b, ["a-very-long-literal-string", "zz"])

    def test_case_when_wide_branch_strings(self):
        b = make_batch([("x", dt.INT32)], {"x": [1, 9]})
        expr = E.CaseWhen(
            [(E.LessThan(Ref(0, dt.INT32), lit(5)),
              lit("quite-a-long-result-string"))], lit("s"))
        check_expr(expr, b, ["quite-a-long-result-string", "s"])

    def test_cast_string_identity(self):
        b = make_batch([("s", dt.STRING)], {"s": ["abc", None]})
        check_expr(E.Cast(Ref(0, dt.STRING), dt.STRING), b, ["abc", None])

    def test_round_bigint_exact(self):
        v = 2**60 + 1
        b = make_batch([("x", dt.INT64)], {"x": [v, -v, 125, None]})
        check_expr(E.Round(Ref(0, dt.INT64), 0), b, [v, -v, 125, None])
        check_expr(E.Round(Ref(0, dt.INT64), -1), b,
                   [1152921504606846980, -1152921504606846980, 130, None])

    def test_host_column_none_string_entries(self):
        # HostColumn permits None entries for nulls; kernels must not crash.
        import numpy as np
        from spark_rapids_tpu.columnar.host import HostColumn
        data = np.empty(2, dtype=object)
        data[0] = b"ok"
        data[1] = None
        hc = HostColumn(dt.STRING, data, np.array([True, False]))
        hb = HostBatch(("s",), [hc])
        check_expr(E.Upper(Ref(0, dt.STRING)), hb, ["OK", None])


class TestNewStringExprs:
    """Round-3 expression breadth (GpuOverrides.scala:537-1667 surface)."""

    def test_concat_ws_skips_nulls(self):
        b = make_batch([("s", dt.STRING), ("t", dt.STRING)],
                       {"s": ["a", None, "c", None],
                        "t": ["x", "y", None, None]})
        check_expr(E.ConcatWs("-", Ref(0, dt.STRING), Ref(1, dt.STRING)),
                   b, ["a-x", "y", "c", ""])

    def test_concat_ws_multi(self):
        b = make_batch(
            [("a", dt.STRING), ("b", dt.STRING), ("c", dt.STRING)],
            {"a": ["1", "1", None], "b": [None, "2", None],
             "c": ["3", "3", None]})
        check_expr(E.ConcatWs(", ", Ref(0, dt.STRING), Ref(1, dt.STRING),
                              Ref(2, dt.STRING)),
                   b, ["1, 3", "1, 2, 3", ""])

    def test_repeat(self):
        b = make_batch([("s", dt.STRING)], {"s": ["ab", "", None, "x"]})
        check_expr(E.StringRepeat(Ref(0, dt.STRING), 3), b,
                   ["ababab", "", None, "xxx"])

    def test_reverse_utf8(self):
        b = make_batch([("s", dt.STRING)],
                       {"s": ["abc", "", None, "héllo", "abé"]})
        check_expr(E.StringReverse(Ref(0, dt.STRING)), b,
                   ["cba", "", None, "olléh", "éba"])

    def test_initcap(self):
        b = make_batch([("s", dt.STRING)],
                       {"s": ["hello world", "fOO bAR", "", None, "a b c"]})
        check_expr(E.InitCap(Ref(0, dt.STRING)), b,
                   ["Hello World", "Foo Bar", "", None, "A B C"])

    def test_regexp_extract(self):
        b = make_batch([("s", dt.STRING)],
                       {"s": ["100-200", "foo", None, "7-8"]})
        check_expr(E.RegExpExtract(Ref(0, dt.STRING), r"(\d+)-(\d+)", 1),
                   b, ["100", "", None, "7"])
        check_expr(E.RegExpExtract(Ref(0, dt.STRING), r"(\d+)-(\d+)", 2),
                   b, ["200", "", None, "8"])

    def test_translate(self):
        b = make_batch([("s", dt.STRING)], {"s": ["abcba", None, "xyz"]})
        check_expr(E.Translate(Ref(0, dt.STRING), "abx", "AB"), b,
                   ["ABcBA", None, "yz"])

    def test_lpad_rpad(self):
        b = make_batch([("s", dt.STRING)], {"s": ["hi", "longer", None]})
        check_expr(E.StringLPad(Ref(0, dt.STRING), 5, "*"), b,
                   ["***hi", "longe", None])
        check_expr(E.StringRPad(Ref(0, dt.STRING), 5, "*"), b,
                   ["hi***", "longe", None])

    def test_lpad_nonpositive_length_is_empty(self):
        # Spark: lpad/rpad with len <= 0 returns '' (not a tail slice).
        b = make_batch([("s", dt.STRING)], {"s": ["hello", "", None]})
        check_expr(E.StringLPad(Ref(0, dt.STRING), -1, "*"), b,
                   ["", "", None])
        check_expr(E.StringRPad(Ref(0, dt.STRING), 0, "*"), b,
                   ["", "", None])

    def test_concat_ws_no_columns(self):
        b = make_batch([("s", dt.STRING)], {"s": ["a", "b"]})
        check_expr(E.ConcatWs("-"), b, ["", ""])


class TestBRound:
    def test_bround_half_even_float(self):
        b = make_batch([("x", dt.FLOAT64)],
                       {"x": [2.5, 3.5, -2.5, 1.25, None]})
        check_expr(E.BRound(Ref(0, dt.FLOAT64), 0), b,
                   [2.0, 4.0, -2.0, 1.0, None])
        check_expr(E.BRound(Ref(0, dt.FLOAT64), 1), b,
                   [2.5, 3.5, -2.5, 1.2, None], approx_float=True)

    def test_bround_int_negative_scale(self):
        b = make_batch([("x", dt.INT64)],
                       {"x": [25, 35, -25, -35, 24, 26, None]})
        check_expr(E.BRound(Ref(0, dt.INT64), -1), b,
                   [20, 40, -20, -40, 20, 30, None])


class TestTruncDate:
    def test_trunc_year_month_quarter_week(self):
        import datetime as pydt
        epoch = pydt.date(1970, 1, 1)
        days = lambda y, m, d: (pydt.date(y, m, d) - epoch).days
        b = make_batch([("d", dt.DATE)],
                       {"d": [days(2020, 7, 17), days(2019, 2, 28), None]})
        check_expr(E.TruncDate(Ref(0, dt.DATE), "year"), b,
                   [days(2020, 1, 1), days(2019, 1, 1), None])
        check_expr(E.TruncDate(Ref(0, dt.DATE), "month"), b,
                   [days(2020, 7, 1), days(2019, 2, 1), None])
        check_expr(E.TruncDate(Ref(0, dt.DATE), "quarter"), b,
                   [days(2020, 7, 1), days(2019, 1, 1), None])
        # 2020-07-17 is a Friday -> Monday 2020-07-13.
        check_expr(E.TruncDate(Ref(0, dt.DATE), "week"), b,
                   [days(2020, 7, 13), days(2019, 2, 25), None])

    def test_trunc_bad_format_is_null(self):
        b = make_batch([("d", dt.DATE)], {"d": [1000, None]})
        check_expr(E.TruncDate(Ref(0, dt.DATE), "bogus"), b, [None, None])


class TestSplitSubstringIndex:
    """StringSplit (element-access form) + SubstringIndex parity against
    a pure-python oracle over data_gen-generated strings (ROADMAP item 5
    expression-gap slice) — the split(...).getItem(i) and
    substring_index shapes that previously forced a host fallback."""

    @staticmethod
    def _py_split(s, d, i):
        if s is None:
            return None
        parts = s.split(d)
        return parts[i] if 0 <= i < len(parts) else None

    @staticmethod
    def _py_ssi(s, d, c):
        if s is None:
            return None
        if c == 0:
            return ""
        parts = s.split(d)
        if c > 0:
            return d.join(parts[:c]) if len(parts) > c else s
        k = -c
        return d.join(parts[-k:]) if len(parts) > k else s

    def _gen_strings(self, delim, n=80):
        """data_gen strings joined with the delimiter so generated rows
        carry 0..3 occurrences (plus the generator's own specials)."""
        from data_gen import StringGen
        rng = np.random.default_rng(99)
        gen = StringGen(nullable=True)
        # Cap piece width: the byte-matrix width drives kernel cost and
        # the parity property is width-independent.
        pieces = [None if p is None else p[:16]
                  for p in gen.gen(rng, n * 2)]
        out = []
        for i in range(n):
            k = int(rng.integers(0, 4))
            parts = [pieces[(i * 3 + j) % len(pieces)] or ""
                     for j in range(k + 1)]
            if pieces[i * 2 % len(pieces)] is None and k == 0:
                out.append(None)
            else:
                out.append(delim.join(parts))
        return out

    @pytest.mark.parametrize("delim", [",", "ab"])
    def test_split_parity(self, delim):
        vals = self._gen_strings(delim)
        b = make_batch([("s", dt.STRING)], {"s": vals})
        for i in (0, 1, 5, -1):
            check_expr(E.StringSplit(Ref(0, dt.STRING), delim, i), b,
                       [self._py_split(v, delim, i) if i >= 0 else None
                        for v in vals])

    @pytest.mark.parametrize("delim", [",", "ab"])
    def test_substring_index_parity(self, delim):
        vals = self._gen_strings(delim)
        b = make_batch([("s", dt.STRING)], {"s": vals})
        for c in (1, 2, -1, 0):
            check_expr(E.SubstringIndex(Ref(0, dt.STRING), delim, c), b,
                       [self._py_ssi(v, delim, c) for v in vals])

    def test_overlapping_multibyte_delimiter(self):
        vals = ["aaa", "aabaa", "aaaa", "xaay", None, "", "aa"]
        b = make_batch([("s", dt.STRING)], {"s": vals})
        for i in (0, 1, 2):
            check_expr(E.StringSplit(Ref(0, dt.STRING), "aa", i), b,
                       [self._py_split(v, "aa", i) for v in vals])
        for c in (1, -1):
            check_expr(E.SubstringIndex(Ref(0, dt.STRING), "aa", c), b,
                       [self._py_ssi(v, "aa", c) for v in vals])

    def test_empty_delimiter_rejected(self):
        with pytest.raises(ValueError):
            E.StringSplit(Ref(0, dt.STRING), "", 0)
        with pytest.raises(ValueError):
            E.SubstringIndex(Ref(0, dt.STRING), "", 1)

    def test_frontend_lowering(self):
        from spark_rapids_tpu.api.dataframe import TpuSession
        from spark_rapids_tpu.plan.logical import (
            col, split, substring_index)
        s = TpuSession()
        df = s.create_dataframe(
            {"s": ["a.b.c", "x", None, "p.q"]}, [("s", dt.STRING)])
        out = df.select(
            split(col("s"), ".", 1).alias("second"),
            substring_index(col("s"), ".", 2).alias("prefix")).collect()
        assert out == [("b", "a.b"), (None, "x"), (None, None),
                       ("q", "p.q")]


class TestMd5:
    """Md5: the vectorized
    device/host MD5 against hashlib over data_gen strings, including
    every padding boundary (55/56/64-byte chunk edges)."""

    @staticmethod
    def _oracle(vals):
        import hashlib
        return [None if v is None
                else hashlib.md5(v.encode("utf-8")).hexdigest()
                for v in vals]

    def test_md5_padding_boundaries(self):
        vals = ["", "abc", "a" * 54, "b" * 55, "c" * 56, "d" * 63,
                "e" * 64, "f" * 65, None, "g" * 119, "h" * 120]
        b = make_batch([("s", dt.STRING)], {"s": vals})
        check_expr(E.Md5(Ref(0, dt.STRING)), b, self._oracle(vals))

    def test_md5_data_gen_parity(self):
        from data_gen import StringGen
        rng = np.random.default_rng(42)
        vals = StringGen(nullable=True).gen(rng, 96)
        b = make_batch([("s", dt.STRING)], {"s": vals})
        check_expr(E.Md5(Ref(0, dt.STRING)), b, self._oracle(vals))

    def test_md5_dataframe_api(self):
        from spark_rapids_tpu.api.dataframe import TpuSession
        from spark_rapids_tpu.plan.logical import col, md5
        s = TpuSession()
        df = s.create_dataframe({"s": ["hello", None, ""]},
                                [("s", dt.STRING)])
        out = df.select(md5(col("s")).alias("h")).collect()
        import hashlib
        assert out == [(hashlib.md5(b"hello").hexdigest(),), (None,),
                       (hashlib.md5(b"").hexdigest(),)]
