"""Wire codec (columnar/wire.py): lossless narrow-upload round trips.

The codec must be invisible: host_to_device(hb) -> device_to_host must
reproduce every value bit-exactly, for every dtype and every adversarial
float (NaN, inf, -0.0, denormals), with and without nulls.
"""

import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar import wire
from spark_rapids_tpu.columnar.host import (HostBatch, HostColumn,
                                            device_to_host, host_to_device)


def roundtrip(dtype, values):
    hb = HostBatch.from_pydict([("x", dtype)], {"x": values})
    db = host_to_device(hb)
    back = device_to_host(db, ("x",))
    return back.columns[0].to_list(), db




def hi_card(base, dtype=None):
    """Append >1024 distinct filler values so the dictionary path declines
    and the typed-wire spec under test is the one chosen."""
    import numpy as np
    if dtype == "str":
        return list(base) + [f"filler-{i}" for i in range(1200)]
    return list(base) + [float(i) + 0.5 if dtype == "f" else (10 + i)
                         for i in range(1200)]

class TestWireRoundTrip:
    def test_int_narrowing_small(self):
        vals = [1, 2, None, 127, -128]
        out, db = roundtrip(dt.INT64, vals)
        assert out == vals
        # Wire dtype must actually be narrow on the encode side.
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.INT64, vals), "x", 5, 8, None)
        assert spec[2] == "int8"

    def test_int_no_narrowing_when_big(self):
        vals = [2 ** 40, -2 ** 40, None]
        out, _ = roundtrip(dt.INT64, vals)
        assert out == vals
        vals = hi_card([2 ** 40, -2 ** 40, None])
        vals += [v * 2 ** 30 for v in range(1300)]   # defeat int narrowing
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.INT64, vals), "x", len(vals), 4096,
            None)
        assert spec[2] == "int64"

    def test_float_2dp_ships_exact(self):
        # 2-decimal money values are NOT exactly a cast away from any
        # narrow type; the codec must NOT invent a scaled-int decode (the
        # device's emulated f64 divide is not correctly rounded), so these
        # ship as f64 (or f32 when exactly representable) and round-trip
        # bit-exactly.
        vals = [1234.56, 0.01, None, -99.99, 0.07]
        out, _ = roundtrip(dt.FLOAT64, vals)
        assert out == vals
        vals = hi_card(vals, "f")
        vals = [None if v is None else v + 0.003 for v in vals]
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.FLOAT64, vals), "x", len(vals),
            4096, None)
        assert spec[2] == "float64"

    def test_float_whole_numbers(self):
        vals = [1.0, 50.0, None, -3.0]
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.FLOAT64, vals), "x", 4, 8, None)
        assert spec[2] == "int8"
        out, _ = roundtrip(dt.FLOAT64, vals)
        assert out == vals

    def test_float_nan_inf_falls_back(self):
        vals = [1.5, float("nan"), float("inf"), None]
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.FLOAT64, vals), "x", 4, 8, None)
        assert spec[2] == "float64"
        out, _ = roundtrip(dt.FLOAT64, vals)
        assert out[0] == 1.5 and np.isnan(out[1]) and out[2] == float("inf")

    def test_long_string_int32_lengths(self):
        # A >32767-byte string forces int32 wire lengths (int16 would wrap
        # and corrupt the data silently).
        big = "x" * 40000
        vals = [big, "short", None]
        out, _ = roundtrip(dt.STRING, vals)
        assert out == vals
        # Dictionary path: int32 lengths survive the dict len-table too.
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.STRING, vals), "x", 3, 8, None)
        assert spec[0] == "dstr" and spec[2] == "int8" and spec[1] > 32767
        # Typed path (high cardinality): int32 wire lengths.
        vals = hi_card([big, "short", None], "str")
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.STRING, vals), "x", len(vals), 4096,
            None)
        assert spec[0] == "str" and spec[2] == "int32"

    def test_negative_zero_preserved(self):
        vals = hi_card([-0.0, 1.0, 2.0], "f")
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.FLOAT64, vals), "x", len(vals),
            4096, None)
        # -0.0 disqualifies the scaled-int path (it would become +0.0).
        assert spec[2] in ("float64", "float32")
        vals = [-0.0, 1.0, 2.0]
        out, _ = roundtrip(dt.FLOAT64, vals)
        assert np.signbit(np.float64(out[0]))

    def test_float_irrational_falls_back(self):
        vals = hi_card([np.pi, np.e, 1 / 3], "f")
        vals = [v + 1 / 3 for v in vals]
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.FLOAT64, vals), "x", len(vals),
            4096, None)
        assert spec[2] == "float64"
        vals = [np.pi, np.e, 1 / 3]
        out, _ = roundtrip(dt.FLOAT64, vals)
        assert out == vals

    def test_f32_exact_representable(self):
        vals = hi_card([0.5, 0.25, 1.0 + 2 ** -20], "f")
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.FLOAT64, vals), "x", len(vals),
            4096, None)
        assert spec[2] == "float32"
        vals = [0.5, 0.25, 1.0 + 2 ** -20]
        out, _ = roundtrip(dt.FLOAT64, vals)
        assert out == vals

    def test_strings_with_nulls(self):
        vals = ["hello", None, "", "wörld"]
        out, db = roundtrip(dt.STRING, vals)
        assert out == vals

    def test_bool(self):
        vals = [True, None, False, True]
        out, _ = roundtrip(dt.BOOL, vals)
        assert out == vals

    def test_all_valid_validity_elided(self):
        vals = [1, 2, 3]
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.INT32, vals), "x", 3, 8, None)
        assert spec[-1] == "all"
        assert len(arrs) == 1     # data only, no validity buffer

    def test_nulls_packed_validity(self):
        vals = [1, None, 3]
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.INT32, vals), "x", 3, 8, None)
        assert spec[-1] == "packed"
        assert arrs[-1].dtype == np.uint8 and arrs[-1].size == 1
        out, db = roundtrip(dt.INT32, vals)
        assert out == vals
        # Padding rows must read as invalid.
        validity = np.asarray(db.columns[0].validity)
        assert not validity[3:].any()

    def test_empty_batch(self):
        out, _ = roundtrip(dt.FLOAT64, [])
        assert out == []

    def test_date_narrows(self):
        vals = [8766, 9131, None, 10956]
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.DATE, vals), "x", 4, 8, None)
        assert spec[2] == "int16"
        out, _ = roundtrip(dt.DATE, vals)
        assert out == vals

    def test_rows_hint_set(self):
        hb = HostBatch.from_pydict([("x", dt.INT32)], {"x": [1, 2, 3]})
        db = host_to_device(hb)
        assert db.rows_hint == 3


class TestDictionaryWire:
    """Low-cardinality columns ship as codes + a value table (the wire's
    LZ4 stand-in: decode is ONE exact gather, no arithmetic)."""

    def test_string_dict(self):
        vals = (["MAIL", "SHIP", None, "AIR"] * 50)[:-1] + ["RAIL"]
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.STRING, vals), "x", len(vals), 256,
            None)
        assert spec[0] == "dstr"
        out, _ = roundtrip(dt.STRING, vals)
        assert out == vals

    def test_float_dict_bit_exact(self):
        base = [0.01 * i for i in range(11)] + [None]
        vals = base * 20
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.FLOAT64, vals), "x", len(vals), 512,
            None)
        assert spec[0] == "dnum" and spec[2] == "int8"
        # -0.0 disqualifies the dict (factorize hashes it equal to +0.0).
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.FLOAT64, [-0.0] + base[:-1] * 20),
            "x", 221, 256, None)
        assert spec[0] == "num"
        out, _ = roundtrip(dt.FLOAT64, vals)
        import numpy as np
        for got, want in zip(out, vals):
            if want is None:
                assert got is None
            else:
                assert np.float64(got).tobytes() == \
                    np.float64(want).tobytes()

    def test_int_dict(self):
        vals = ([2 ** 40, -2 ** 40, 7, None] * 40)
        out, _ = roundtrip(dt.INT64, vals)
        assert out == vals

    def test_padding_rows_decode_to_zero(self):
        vals = [5.5, 6.5]
        hb = HostBatch.from_pydict([("x", dt.FLOAT64)], {"x": vals * 80})
        db = host_to_device(hb)
        import numpy as np
        data = np.asarray(db.columns[0].data)
        assert (data[160:] == 0).all()


class TestCodecV2:
    """RLE / delta / frame-of-reference (codec v2): chosen by smallest
    wire size from host stats, decoded by gathers + exact integer
    arithmetic, bit-exact round trips per dtype."""

    def test_rle_sorted_floats(self):
        vals = [1.5] * 30 + [2.25] * 30 + [None] * 4 + [7.0] * 30
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.FLOAT64, vals), "x", len(vals),
            128, None)
        assert spec[0] == "rle"
        out, _ = roundtrip(dt.FLOAT64, vals)
        assert out == vals

    def test_rle_bit_view_signed_zero_and_nan(self):
        # Run detection is on the BIT view: -0.0/0.0 and NaN runs must
        # not merge (a value-compare diff would fold them together and
        # gather the wrong bit pattern).
        vals = [-0.0] * 12 + [0.0] * 12 + [float("nan")] * 12 \
            + [1e300] * 12
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.FLOAT64, vals), "x", len(vals),
            48, None)
        assert spec[0] == "rle"
        out, _ = roundtrip(dt.FLOAT64, vals)
        assert np.signbit(np.float64(out[0]))
        assert not np.signbit(np.float64(out[12]))
        assert np.isnan(out[24]) and out[36] == 1e300

    def test_delta_monotone_int64(self):
        # int8 deltas over a span past uint8 (so frame-of-reference
        # needs 2-byte offsets and delta's 1-byte diffs win): the codec
        # ships an int64 base + int8 deltas, decoded by exact cumsum.
        vals = [2 ** 40 + 7 * i for i in range(64)]
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.INT64, vals), "x", 64, 64, None)
        assert spec[0] == "delta" and spec[2] == "int8", spec
        out, _ = roundtrip(dt.INT64, vals)
        assert out == vals

    def test_delta_overflowing_diffs_decline(self):
        # Diffs that wrap int64 must either reconstruct exactly or
        # decline — never corrupt.
        vals = [-(2 ** 62), 2 ** 62, -(2 ** 62), 2 ** 62] * 16
        out, _ = roundtrip(dt.INT64, vals)
        assert out == vals

    def test_for_clustered_int64(self):
        rng = np.random.default_rng(0)
        vals = (10 ** 15 + rng.integers(0, 40_000, 64)).tolist()
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.INT64, vals), "x", 64, 64, None)
        assert spec[0] == "for" and spec[2] == "uint16"
        out, _ = roundtrip(dt.INT64, vals)
        assert out == vals

    def test_v2_padding_rows_decode_to_zero(self):
        for vals in ([3.5] * 40,                        # rle
                     [10 ** 15 + i * 7 for i in range(40)]):  # delta/for
            t = dt.FLOAT64 if isinstance(vals[0], float) else dt.INT64
            hb = HostBatch.from_pydict([("x", t)], {"x": vals})
            db = host_to_device(hb)
            data = np.asarray(db.columns[0].data)
            assert (data[len(vals):] == 0).all()
            assert not np.asarray(db.columns[0].validity)[len(vals):].any()

    def test_property_roundtrip_dtype_ladder(self):
        """Per-dtype property test: adversarial random data AND its
        sorted variant (the RLE/delta-friendly shape) round-trip
        bit-exactly through whatever codec wins."""
        import sys
        sys.path.insert(0, "tests")
        from data_gen import ALL_GENS, gen_batch
        import math
        for gen in ALL_GENS:
            for do_sort in (False, True):
                hb = gen_batch([("x", gen)], 96, seed=17)
                vals = hb.columns[0].to_list()
                if do_sort:
                    nn = [v for v in vals if v is not None]
                    nn.sort(key=lambda v: (
                        isinstance(v, float) and math.isnan(v), v))
                    vals = nn + [None] * 4
                out, _ = roundtrip(gen.dtype, vals)
                for got, want in zip(out, vals):
                    if want is None or got is None:
                        assert got is None and want is None, \
                            (gen.dtype.name, got, want)
                    elif isinstance(want, float):
                        assert np.float64(got).tobytes() == \
                            np.float64(want).tobytes() or (
                                np.isnan(got) and np.isnan(want)), \
                            (gen.dtype.name, got, want)
                    else:
                        assert got == want, (gen.dtype.name, got, want)

    def test_plain_and_v1_modes(self):
        from spark_rapids_tpu.config import TpuConf
        vals = [1.5] * 30 + [None] * 2 + [2.5] * 30
        try:
            wire.maybe_configure(TpuConf(
                {"spark.rapids.sql.wire.codec": "plain"}))
            arrs, spec = wire.encode_column(
                HostColumn.from_values(dt.FLOAT64, vals), "x",
                len(vals), 64, None)
            assert spec[0] == "num" and spec[2] == "float64"
            assert roundtrip(dt.FLOAT64, vals)[0] == vals
            wire.maybe_configure(TpuConf(
                {"spark.rapids.sql.wire.codec": "v1"}))
            arrs, spec = wire.encode_column(
                HostColumn.from_values(dt.FLOAT64, vals), "x",
                len(vals), 64, None)
            assert spec[0] in ("num", "dnum")       # never rle in v1
            assert roundtrip(dt.FLOAT64, vals)[0] == vals
        finally:
            wire.maybe_configure(TpuConf())
        arrs, spec = wire.encode_column(
            HostColumn.from_values(dt.FLOAT64, vals), "x", len(vals),
            64, None)
        assert spec[0] == "rle"                     # back to v2


class TestStagingBuffer:
    """Packed staging uploads: one aligned buffer, one device_put call
    over its typed views, and grouped tiny batches share a call
    bit-identically."""

    def test_offsets_aligned_and_layout_matches(self):
        hb = HostBatch.from_pydict(
            [("a", dt.INT64), ("b", dt.FLOAT64), ("s", dt.STRING)],
            {"a": [1, None, 3], "b": [1.5, 2.5, None],
             "s": ["xy", None, "zzz"]})
        enc = wire.pack_batch(hb)
        entries, total = wire._batch_layout(enc.cap, enc.specs)
        assert enc.staging.nbytes == total
        for off, _name, _shape, _nbytes in entries:
            assert off % 8 == 0

    def test_grouped_upload_bit_identical(self):
        hbs = [HostBatch.from_pydict(
            [("a", dt.INT64), ("b", dt.FLOAT64)],
            {"a": [i, None, i + 2], "b": [i + 0.5, 0.25 * i, None]})
            for i in range(6)]
        solo = [wire.upload_packed(wire.pack_batch(hb)) for hb in hbs]
        grouped = wire.upload_packed_group(
            [wire.pack_batch(hb) for hb in hbs])
        for a, b in zip(solo, grouped):
            from spark_rapids_tpu.columnar.host import device_to_host
            ra = device_to_host(a, ("a", "b")).to_pylist()
            rb = device_to_host(b, ("a", "b")).to_pylist()
            assert ra == rb

    @pytest.mark.parametrize("members", [1, 3])
    def test_counters_tell_calls_from_transfers(self, members):
        # A device_put call moves one transfer per wire ARRAY: the
        # counters must not pass a call off as a link transfer.
        encs = [wire.pack_batch(HostBatch.from_pydict(
            [("a", dt.INT64), ("b", dt.FLOAT64)],
            {"a": [i, None, i + 2], "b": [i + 0.5, 0.25 * i, None]}))
            for i in range(members)]
        arrays = sum(len(wire._batch_layout(e.cap, e.specs)[0])
                     for e in encs)
        assert arrays > members          # several arrays per batch
        c0 = wire.counters()
        wire.upload_packed_group(encs)
        c1 = wire.counters()
        moved = {k: c1.get(k, 0) - c0.get(k, 0) for k in (
            "uploadCalls", "uploadTransfers", "uploadedBatches")}
        assert moved == {"uploadCalls": 1, "uploadTransfers": arrays,
                         "uploadedBatches": members}

    def test_plan_upload_groups(self):
        # Tiny members accumulate to the threshold; big ones ship alone.
        assert wire.plan_upload_groups([10, 20, 2000, 5, 5, 5], 100) \
            == [[0, 1], [2], [3, 4, 5]]
        assert wire.plan_upload_groups([50, 60, 10], 100) \
            == [[0, 1], [2]]
        assert wire.plan_upload_groups([], 100) == []
        assert wire.plan_upload_groups([500], 100) == [[0]]
