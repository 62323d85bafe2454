"""UDF tier (ref udf-compiler/Instruction.scala +
CatalystExpressionBuilder.scala for compilation,
GpuArrowEvalPythonExec.scala:494 for the python fallback): AST
compilation of the restricted subset, the host-roundtrip fallback with
explain visibility, and fuzzed equivalence of compiled UDFs against
direct python application."""

import math
import random

import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.plan.logical import col
from spark_rapids_tpu.udf import UdfCompileError, compile_udf, udf


@pytest.fixture
def session():
    return TpuSession()


@pytest.fixture
def df(session):
    return session.create_dataframe(
        {"x": [1.0, 2.5, -3.0, 4.0, None],
         "y": [10.0, 0.5, 2.0, -1.0, 3.0],
         "s": ["Ab", "cD", None, "ef", "GH"]},
        [("x", srt.FLOAT64), ("y", srt.FLOAT64), ("s", srt.STRING)],
        num_partitions=2)


class TestCompile:
    def test_lambda_arithmetic_compiles(self):
        f = udf(lambda a, b: a * 2.0 + b - 1.5)
        assert f.compiled

    def test_def_with_conditional_compiles(self):
        @udf
        def clamp(a, lo, hi):
            return lo if a < lo else (hi if a > hi else a)
        assert clamp.compiled

    def test_builtins_compile(self):
        assert udf(lambda a, b: min(abs(a), max(b, 1.0))).compiled
        assert udf(lambda s: len(s)).compiled
        assert udf(lambda s: s.upper()).compiled

    def test_loop_does_not_compile(self):
        @udf
        def total(a):
            out = 0
            for i in range(3):
                out += a
            return out
        assert not total.compiled
        assert "single return" in total.compile_error

    def test_captured_literal_inlines(self, session):
        k = 7.0
        f = udf(lambda a: a + k)
        assert f.compiled
        df = session.create_dataframe(
            {"a": [1.0, 2.0]}, [("a", srt.FLOAT64)])
        assert df.select(f(col("a")).alias("z")).collect() == \
            [(8.0,), (9.0,)]

    def test_nonliteral_capture_does_not_compile(self):
        table = {1: 2}
        f = udf(lambda a: table)
        assert not f.compiled
        assert "captured variable" in f.compile_error

    def test_unknown_call_does_not_compile(self):
        f = udf(lambda a: math.erf(a))
        assert not f.compiled


class TestExecution:
    def test_compiled_udf_runs_on_device(self, df):
        f = udf(lambda a, b: a * 2.0 + b)
        q = df.select("x", f(col("x"), col("y")).alias("z"))
        dev = q.collect()
        host = q.collect_host()
        assert dev == host
        for x, y, z in [(r[0], None, r[1]) for r in dev if r[0] is None]:
            assert z is None
        report = q.explain()
        assert "pyudf" not in report     # native expressions, no fallback

    def test_fallback_udf_matches_python(self, df):
        f = udf(lambda a: math.erf(a) if a is not None else None,
                return_type="double")
        assert not f.compiled
        q = df.select("x", f(col("x")).alias("z"))
        dev = dict(q.collect())
        host = dict(q.collect_host())
        assert dev == host
        for x, z in dev.items():
            if x is not None:
                assert z == pytest.approx(math.erf(x))

    def test_fallback_reason_in_explain(self, df):
        f = udf(lambda a: math.erf(a) if a is not None else None,
                return_type="double")
        report = df.select(f(col("x")).alias("z")).explain()
        assert "could not be compiled" in report

    def test_fallback_after_filter(self, df):
        """Selection vectors reach the host roundtrip correctly."""
        f = udf(lambda a: math.floor(a * 10.0) if a is not None else None,
                return_type="double")
        q = df.filter(col("y") > 0).select("x", f(col("x")).alias("z"))
        assert sorted(q.collect(), key=repr) == \
            sorted(q.collect_host(), key=repr)

    def test_string_udf(self, df):
        f = udf(lambda s: s.upper())
        s2 = TpuSession()
        s2.set("spark.rapids.sql.incompatibleOps.enabled", True)
        df2 = s2.create_dataframe(
            {"s": ["Ab", "cD", None]}, [("s", srt.STRING)])
        q = df2.select(f(col("s")).alias("u"))
        assert q.collect() == q.collect_host() == [("AB",), ("CD",),
                                                   (None,)]


class TestFuzzedEquivalence:
    """Random expressions from the compilable grammar: compiled-UDF
    results must equal direct python application (the udf-compiler test
    ideology — OpcodeSuite's equivalence checks)."""

    def _gen_expr(self, rng, depth=0):
        leaves = ["a", "b", "1.5", "2.0", "0.25"]
        if depth > 2 or rng.random() < 0.3:
            return rng.choice(leaves)
        kind = rng.choice(["bin", "call", "cond"])
        if kind == "bin":
            op = rng.choice(["+", "-", "*"])
            return (f"({self._gen_expr(rng, depth + 1)} {op} "
                    f"{self._gen_expr(rng, depth + 1)})")
        if kind == "call":
            fn = rng.choice(["abs", "min", "max"])
            if fn == "abs":
                return f"abs({self._gen_expr(rng, depth + 1)})"
            return (f"{fn}({self._gen_expr(rng, depth + 1)}, "
                    f"{self._gen_expr(rng, depth + 1)})")
        return (f"({self._gen_expr(rng, depth + 1)} if "
                f"{self._gen_expr(rng, depth + 1)} > 0.0 else "
                f"{self._gen_expr(rng, depth + 1)})")

    @pytest.mark.parametrize("seed", range(8))
    def test_fuzzed(self, session, seed, tmp_path):
        rng = random.Random(seed)
        src = f"lambda a, b: {self._gen_expr(rng)}"
        # The compiler reads real source; give the lambda a file.
        mod = tmp_path / f"udf_fuzz_{seed}.py"
        mod.write_text(f"f = {src}\n")
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            f"udf_fuzz_{seed}", mod)
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
        f = m.f
        cf = udf(f)
        assert cf.compiled, src
        xs = [rng.uniform(-5, 5) for _ in range(40)]
        ys = [rng.uniform(-5, 5) for _ in range(40)]
        df = session.create_dataframe(
            {"a": xs, "b": ys},
            [("a", srt.FLOAT64), ("b", srt.FLOAT64)], num_partitions=2)
        got = [r[0] for r in
               df.select(cf(col("a"), col("b")).alias("z")).collect()]
        want = [f(x, y) for x, y in zip(xs, ys)]
        assert got == pytest.approx(want), src
