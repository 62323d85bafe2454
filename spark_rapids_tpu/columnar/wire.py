"""Wire codec for host->device uploads: narrow dtypes + packed validity.

TPU-first re-design of the reference's GPU parquet decode
(GpuParquetScan.scala:1144 keeps *compressed pages* on the transfer path and
decodes on-device with cuDF). XLA has no byte-oriented snappy kernel, but the
same bandwidth win comes from a typed transform: before upload each column is
analyzed (vectorized numpy, one pass) and, when lossless, re-encoded to a
narrower wire type --

- integers whose [min, max] fits int8/int16/int32 ship narrow;
- float64 columns whose values are whole numbers in int32 range ship as
  ints (decoded by a pure int->f64 cast);
- float64 exactly representable as float32 ships as float32;
- all-valid validity vanishes (reconstructed from the row mask); otherwise
  it ships as packed bits (1/8th);
- string length columns ship int16 when the column width bounds them,
  int32 otherwise.

Only pure dtype CASTS are used on the device side. The TPU's float64 is
double-double emulation whose arithmetic (add/mul/div) is NOT correctly
rounded (measured ~2 ulps off), so any decode that computes — e.g. a
scaled-decimal ``int / 100`` — lands on a different f64 than the host
value and silently breaks bit-exact comparisons downstream (a filter
``x <= 0.07`` dropped every 0.07 row). Casts int<->f64 and f32->f64 are
exact on the emulated backend (verified), so the codec restricts itself
to them.

The device side widens back to the logical dtype inside ONE jitted decode
program per (capacity, spec) -- a few fused casts, so HBM traffic is the
only cost there. The host->device link (PCIe: 4.7 GB/s for a 256 MB
device_put on the attached v5e, PR 21, against 819 GB/s of HBM) is the
scarce resource this trades against; reconstruction is bit-exact by
construction, so every engine invariant (zeroed padding, validity masking)
is preserved.

Codec v2 (``spark.rapids.sql.wire.codec``, default ``v2``) extends the
typed transform with three more lossless encodings, chosen per column
from one cheap host stats pass by smallest wire size:

- **RLE** for sorted / low-run-count columns: run values + exclusive run
  end offsets; the device decode is a ``searchsorted`` over the run ends
  plus one gather (float runs are detected on the BIT view, so ``-0.0``
  vs ``0.0`` and distinct NaN payloads never merge).
- **delta** for monotone/smooth integer columns: an int64 base + narrow
  int deltas, decoded by a jitted integer cumsum (two's-complement
  arithmetic is wrap-identical between numpy and XLA, and the encoder
  verifies the round trip before committing).
- **frame-of-reference** for clustered int64/int32 (ids in a dense
  band far from zero): an int64 base + narrow unsigned offsets, decoded
  by one exact integer add.

``v1`` keeps the original dictionary + narrow-int behavior; ``plain``
ships the logical dtypes untransformed (the transport-transparency
baseline the dual-engine parity suite pins).

All of a batch's wire arrays are additionally PACKED, on the host, into
one contiguous 8-byte-aligned staging buffer with a static offset table
(pure CPU work, done on prefetch threads). An upload is one
``jax.device_put`` CALL over the typed, zero-copy VIEWS of that buffer --
one host-to-device transfer PER wire array, not one per batch -- plus one
jitted decode program; the device never sees the byte image. (Until PR 21
the raw uint8 buffer crossed the link once and a jitted program sliced and
bitcast it apart on the device; the v5e compiler turned those
``u8[n, itemsize]`` reshapes into 343 MB of code in 698 s for q6's batch
shape alone.) Consecutive tiny batches (below
``spark.rapids.sql.wire.minUploadBytes``) share a ``device_put`` call via
``upload_packed_group``: that saves call overhead, not transfers.

Since the buffer no longer crosses the link whole, packing is a host copy
that buys nothing the encoded arrays do not already give ``device_put``;
whether the pack, the layout table and the grouping go is ROADMAP A2's to
measure (``scripts/chip_probe.py`` times the three upload shapes).
"""

from __future__ import annotations

import dataclasses
import os
import struct
import threading
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import faults
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import DeviceBatch, DeviceColumn


# ---------------------------------------------------------------------------
# Integrity framing for serialized batch blobs (spill frames, any future
# inter-process shuffle wire). A 16-byte header: magic | CRC32 | length.
# Deserialize verifies ALL THREE, so a flipped bit / truncated write /
# foreign blob raises WireCorruptionError at the frame boundary instead
# of np.frombuffer silently reinterpreting garbage into wrong rows.
# ---------------------------------------------------------------------------

_FRAME_MAGIC = b"SRTW"
_FRAME_HEADER = struct.Struct("<4sIQ")      # magic, crc32, payload length


class WireCorruptionError(ValueError):
    """A serialized frame failed its integrity check at deserialize."""


def frame_blob(blob: bytes) -> bytes:
    """Wrap ``blob`` in the checksummed wire frame."""
    return _FRAME_HEADER.pack(_FRAME_MAGIC, zlib.crc32(blob) & 0xFFFFFFFF,
                              len(blob)) + blob


def unframe_blob(framed: bytes) -> bytes:
    """Verify + strip the wire frame; raises :class:`WireCorruptionError`
    on any mismatch (magic, length, or CRC32)."""
    if len(framed) < _FRAME_HEADER.size:
        raise WireCorruptionError(
            f"frame truncated: {len(framed)} bytes < header")
    magic, crc, length = _FRAME_HEADER.unpack_from(framed)
    if magic != _FRAME_MAGIC:
        raise WireCorruptionError(f"bad frame magic {magic!r}")
    payload = framed[_FRAME_HEADER.size:]
    if len(payload) != length:
        raise WireCorruptionError(
            f"frame length mismatch: header says {length}, "
            f"payload is {len(payload)}")
    actual = zlib.crc32(payload) & 0xFFFFFFFF
    if actual != crc:
        raise WireCorruptionError(
            f"frame CRC32 mismatch: header {crc:#010x}, "
            f"payload {actual:#010x}")
    return payload

# ---------------------------------------------------------------------------
# Codec mode (spark.rapids.sql.wire.codec / SRT_WIRE_CODEC): process-global,
# like the kernel cache — concurrent sessions with conflicting explicit
# settings race to last-write (documented; the CI matrix uses the env).
# ---------------------------------------------------------------------------

CODEC_MODES = ("plain", "v1", "v2")
_CODEC_OVERRIDE: Optional[str] = None


def codec_mode() -> str:
    if _CODEC_OVERRIDE is not None:
        return _CODEC_OVERRIDE
    env = os.environ.get("SRT_WIRE_CODEC", "").strip().lower()
    return env if env in CODEC_MODES else "v2"


def maybe_configure(conf) -> None:
    """Adopt an explicitly-set ``spark.rapids.sql.wire.codec`` for the
    process (unset clears any prior override back to env/default)."""
    global _CODEC_OVERRIDE
    from spark_rapids_tpu import config as C
    raw = conf.raw.get(C.WIRE_CODEC.key)
    if raw is None:
        _CODEC_OVERRIDE = None
        return
    mode = str(raw).strip().lower()
    if mode not in CODEC_MODES:
        raise ValueError(f"unknown wire codec {raw!r}; "
                         f"expected one of {CODEC_MODES}")
    _CODEC_OVERRIDE = mode


# Process-global transport counters (``counters()``; chip_smoke.py and
# the telemetry registry read them):
# rawBytes = decoded device footprint the plain codec would have shipped,
# encodedBytes = wire arrays actually produced, stagingBytes = packed
# staging buffers built, uploadTransfers = arrays handed to device_put
# (each is its own host-to-device transfer), uploadCalls vs
# uploadedBatches = how many device_put calls served how many batches
# (grouping shows as calls < batches), codecCols.<kind> = per-codec
# column counts.
_WIRE_LOCK = threading.Lock()
_WIRE_COUNTERS: Dict[str, float] = {}


def _wrecord(name: str, amount: float = 1) -> None:
    with _WIRE_LOCK:
        _WIRE_COUNTERS[name] = _WIRE_COUNTERS.get(name, 0) + amount


def counters() -> Dict[str, float]:
    with _WIRE_LOCK:
        out = dict(_WIRE_COUNTERS)
    raw = out.get("rawBytes", 0)
    if raw > 0:
        out["wireCompressionRatio"] = round(
            raw / max(out.get("encodedBytes", raw), 1), 4)
    batches = out.get("uploadedBatches", 0)
    if batches > 0:
        # Fraction of batches that shared a device_put call with a
        # neighbor (0 = every batch paid its own call).
        out["stagingHitRate"] = round(
            1.0 - out.get("uploadCalls", batches) / batches, 4)
    return out


def reset_counters() -> None:
    with _WIRE_LOCK:
        _WIRE_COUNTERS.clear()


# Column wire spec (static, hashable -- part of the decode jit cache key):
#   numeric: ("num", logical_name, wire_np_name, vmode)
#   string:  ("str", width, lengths_np_name, vmode)
#   dict num: ("dnum", logical_name, code_np_name, dict_cap, vmode)
#   dict str: ("dstr", width, code_np_name, dict_cap, vmode)
#   RLE:      ("rle", logical_name, value_np_name, run_cap, vmode)
#   delta:    ("delta", logical_name, delta_np_name, vmode)
#   frame-of-reference: ("for", logical_name, offset_np_name, vmode)
# vmode: "all" (validity == row mask) | "packed" (bit-packed uint8).
#
# Dictionary encoding is the LZ4-of-this-wire (NvcompLZ4CompressionCodec
# analog): XLA cannot run a byte-serial decompressor, but a gather from a
# small value table is one exact fused kernel — and TPC-shaped data is
# full of low-cardinality columns (flags, modes, quantities, discounts)
# where an 8-byte float or an 8..32-byte string row ships as a 1-2 byte
# code. Exactness: the gathered values ARE the host bit patterns (no
# arithmetic), so emulated-f64 rounding never enters.

_DICT_MAX = 4096            # value-table entries worth a table gather
_DICT_SAMPLE = 1 << 16


def _try_dict(values: np.ndarray, n: int):
    """(codes, uniques) via pandas factorize when cardinality is low
    enough to pay off, else None. Codes are -1-free (values prefiltered
    for NaN; nulls were zeroed upstream)."""
    if n == 0:
        return None
    if values.dtype.kind == "f":
        v = values[:n]
        # factorize hashes -0.0 == 0.0, which would drop the sign bit.
        if not np.isfinite(v).all() or np.any((v == 0) & np.signbit(v)):
            return None
    sample = values[:min(n, _DICT_SAMPLE)]
    if len(np.unique(sample)) > _DICT_MAX // 4:
        return None
    import pandas as pd
    codes, uniques = pd.factorize(values[:n], sort=False)
    if len(uniques) > _DICT_MAX:
        return None
    return codes, uniques

_INT_CANDIDATES = (
    (np.int8, -128, 127),
    (np.int16, -32768, 32767),
    (np.int32, -(2 ** 31), 2 ** 31 - 1),
)

def _narrow_int(values: np.ndarray, itemsize: int):
    """Smallest int dtype whose range covers values (None = keep)."""
    if values.size == 0:
        return np.int8
    mn = values.min()
    mx = values.max()
    for cand, lo, hi in _INT_CANDIDATES:
        if np.dtype(cand).itemsize >= itemsize:
            return None
        if lo <= mn and mx <= hi:
            return cand
    return None


def _encode_float64(values: np.ndarray):
    """Returns (wire_array, wire_np_name) or None. Lossless only, and the
    device decode must be a pure CAST (emulated-f64 arithmetic is not
    correctly rounded — see module docstring): whole numbers in int32
    range ship as narrow ints; exactly-f32-representable ships as f32.
    NaN/inf/-0.0 disqualify the int path (-0.0 would become +0.0)."""
    with np.errstate(invalid="ignore"):
        finite = np.isfinite(values).all() if values.size else True
    if finite and not (values.size
                       and np.any((values == 0) & np.signbit(values))):
        r = np.rint(values)
        if not np.any(np.abs(r) > 2 ** 31 - 1) \
                and np.array_equal(r, values):
            narrow = _narrow_int(r, 8) or np.int32
            return r.astype(narrow), np.dtype(narrow).name
    with np.errstate(over="ignore"):
        f32 = values.astype(np.float32)
    if np.array_equal(f32.astype(np.float64), values):
        return f32, "float32"
    return None


# -- codec v2 candidates ------------------------------------------------------
# Each _try_* returns (wire_arrays, spec_tail, wire_bytes) or None. They
# compete on wire_bytes against the typed/dict encodings; the decode for
# every one of them is gathers + exact integer arithmetic only, never
# emulated-f64 math (see module docstring).

def _bit_view(v: np.ndarray) -> np.ndarray:
    """Float values as their bit patterns (run/equality detection must
    distinguish -0.0 from 0.0 and NaN payloads; int passthrough)."""
    if v.dtype.kind == "f":
        return v.view(np.int32 if v.dtype.itemsize == 4 else np.int64)
    return v


def _try_rle(wire: np.ndarray, n: int, cap: int):
    """Run-length encoding over the (already narrowed) wire values:
    run values + ascending exclusive run-end offsets. Decode is
    searchsorted(run_ends, row) + one table gather — bit patterns move
    untouched. Worth it only when runs are rare (sorted or clustered
    columns)."""
    from spark_rapids_tpu.columnar.batch import bucket_capacity
    if n < 8:
        return None
    v = wire[:n]
    bits = _bit_view(v)
    starts = np.empty(n, np.bool_)
    starts[0] = True
    np.not_equal(bits[1:], bits[:-1], out=starts[1:])
    runs = int(starts.sum())
    if runs > n // 4:
        return None
    run_cap = bucket_capacity(max(runs, 1))
    sidx = np.flatnonzero(starts)
    run_vals = np.zeros(run_cap, v.dtype)
    run_vals[:runs] = v[sidx]
    # Exclusive end of run i; padding entries sit at cap so padding rows
    # index past the real runs into zeroed table slots.
    ends = np.full(run_cap, cap, np.int32)
    if runs > 1:
        ends[:runs - 1] = sidx[1:]
    ends[runs - 1] = n
    nbytes = run_cap * (v.dtype.itemsize + 4)
    return [run_vals, ends], (v.dtype.name, run_cap), nbytes


_DELTA_CANDIDATES = (np.int8, np.int16, np.int32)


def _smallest_int(lo: int, hi: int, max_itemsize: int):
    """Smallest signed int dtype strictly narrower than ``max_itemsize``
    covering [lo, hi], or None."""
    for cand, clo, chi in _INT_CANDIDATES:
        if np.dtype(cand).itemsize >= max_itemsize:
            return None
        if clo <= lo and hi <= chi:
            return cand
    return None


def _try_delta(wire: np.ndarray, n: int, cap: int):
    """Delta encoding for monotone/smooth integer columns: int64 base +
    narrow int deltas, decoded by a jitted int64 cumsum. Two's-complement
    wrap is identical between numpy and XLA, and the encoder verifies the
    reconstruction before committing, so the decode is exact by
    construction."""
    if n < 8 or wire.dtype.kind != "i" or wire.dtype.itemsize < 4:
        return None
    v64 = wire[:n].astype(np.int64)
    d = np.diff(v64)
    if d.size == 0:
        return None
    narrow = _smallest_int(int(d.min()), int(d.max()), wire.dtype.itemsize)
    if narrow is None:
        return None
    # Round-trip proof (covers any int64 diff wraparound): base +
    # cumsum(deltas) must reproduce the values bit-for-bit.
    if not np.array_equal(
            v64[0] + np.concatenate([np.zeros(1, np.int64),
                                     d]).cumsum(dtype=np.int64), v64):
        return None
    deltas = np.zeros(cap, narrow)
    deltas[1:n] = d.astype(narrow)
    base = np.asarray([v64[0]], np.int64)
    nbytes = 8 + cap * np.dtype(narrow).itemsize
    return [base, deltas], (np.dtype(narrow).name,), nbytes


_FOR_CANDIDATES = ((np.uint8, 0xFF), (np.uint16, 0xFFFF),
                   (np.uint32, 0xFFFFFFFF))


def _try_for(wire: np.ndarray, n: int, cap: int):
    """Frame-of-reference narrowing for clustered integers far from zero
    (dense id bands): int64 base = min + narrow unsigned offsets, decoded
    by one exact integer add."""
    if n == 0 or wire.dtype.kind != "i" or wire.dtype.itemsize < 4:
        return None
    v = wire[:n]
    vmin, vmax = int(v.min()), int(v.max())
    span = vmax - vmin
    narrow = None
    for cand, hi in _FOR_CANDIDATES:
        if np.dtype(cand).itemsize >= wire.dtype.itemsize:
            break
        if span <= hi:
            narrow = cand
            break
    if narrow is None:
        return None
    offsets = np.zeros(cap, narrow)
    offsets[:n] = (v - vmin).astype(narrow)
    base = np.asarray([vmin], np.int64)
    nbytes = 8 + cap * np.dtype(narrow).itemsize
    return [base, offsets], (np.dtype(narrow).name,), nbytes


def encode_column(hc, name: str, n: int, cap: int,
                  string_widths: Optional[dict]) -> Tuple[List[np.ndarray],
                                                          tuple]:
    """Host-side encode of one column -> (wire arrays, static spec),
    under the active codec mode. Counters record the decoded (raw)
    footprint vs the wire bytes and the chosen codec kind."""
    arrs, spec = _encode_column_impl(hc, name, n, cap, string_widths,
                                     codec_mode())
    raw = cap * (hc.dtype.itemsize + 1)
    if hc.dtype.is_string:
        raw = cap * (spec[1] + 4 + 1)      # matrix + lengths + validity
    _wrecord("rawBytes", raw)
    _wrecord("encodedBytes", sum(a.nbytes for a in arrs))
    _wrecord(f"codecCols.{spec[0]}")
    return arrs, spec


def _encode_column_impl(hc, name: str, n: int, cap: int,
                        string_widths: Optional[dict], mode: str
                        ) -> Tuple[List[np.ndarray], tuple]:
    from spark_rapids_tpu.columnar.host import strings_to_matrix
    validity = np.zeros(cap, dtype=np.bool_)
    validity[:n] = hc.validity
    all_valid = bool(validity[:n].all())
    if all_valid:
        vmode, varrs = "all", []
    else:
        vmode = "packed"
        varrs = [np.packbits(validity, bitorder="little")]

    if hc.dtype.is_string:
        # Dictionary path first: a low-cardinality string column (flags,
        # modes, segments) ships 1-2 byte codes + a tiny value table
        # instead of a (rows x width) byte matrix. All probing runs on
        # the dense byte MATRIX (never the lazy per-row object array):
        # rows keyed as (big-endian length | content bytes) void scalars,
        # compared bytewise by np.unique — fully vectorized.
        m0, lens0 = strings_to_matrix(hc)
        lens0 = np.where(hc.validity, lens0, 0).astype(np.int32)
        mw = m0.shape[1]
        d = None
        if n and mode != "plain":
            keyed = np.zeros((n, mw + 4), np.uint8)
            keyed[:, :4] = lens0.astype(">i4").view(np.uint8) \
                .reshape(n, 4)
            if mw:
                keyed[:, 4:] = np.where(hc.validity[:, None],
                                        m0[:n], 0)
            key = np.ascontiguousarray(keyed).view(
                [("k", f"V{mw + 4}")]).ravel()
            if len(np.unique(key[:_DICT_SAMPLE])) <= _DICT_MAX // 4:
                uniq, first_idx, codes = np.unique(
                    key, return_index=True, return_inverse=True)
                if len(uniq) <= _DICT_MAX:
                    d = (codes, first_idx)
        if d is not None:
            codes, first_idx = d
            k = len(first_idx)
            ulens = lens0[first_idx]
            want = dt.string_width_bucket(int(ulens.max()) if k else 0)
            if string_widths and name in string_widths:
                want = max(want, string_widths[name])
            # The all-zero key (empty/invalid rows) is the code padding
            # rows take; add one if the column had no empty strings.
            zeros = np.flatnonzero(ulens == 0)
            dict_rows = list(first_idx)
            if zeros.size:
                zero_code = int(zeros[0])
            else:
                dict_rows.append(None)
                zero_code = k
                k += 1
            dict_cap = 8
            while dict_cap < k:
                dict_cap *= 2
            table = np.zeros((dict_cap, want), dtype=np.uint8)
            len_t = np.int16 if want <= 32767 else np.int32
            len_table = np.zeros(dict_cap, dtype=len_t)
            w = min(want, mw)
            for i, ri in enumerate(dict_rows):
                if ri is None:
                    continue
                if w:
                    table[i, :w] = np.where(hc.validity[ri],
                                            m0[ri, :w], 0)
                len_table[i] = min(int(ulens[i]) if i < len(ulens)
                                   else 0, want)
            code_t = np.int8 if dict_cap <= 128 else np.int16
            codes_arr = np.full(cap, zero_code, dtype=code_t)
            codes_arr[:n] = codes
            return [codes_arr, table, len_table] + varrs, \
                ("dstr", want, np.dtype(code_t).name, dict_cap, vmode)
        m, lens = m0, lens0
        lens = np.where(hc.validity, lens, 0)
        want = dt.string_width_bucket(int(lens.max()) if n else 0)
        if string_widths and name in string_widths:
            want = max(want, string_widths[name])
        data = np.zeros((cap, want), dtype=np.uint8)
        w = min(want, m.shape[1])
        data[:n, :w] = np.where(hc.validity[:, None], m, 0)[:, :w]
        # Lengths are bounded by the column width: int16 only when the
        # width itself fits (a >32767-byte string would otherwise wrap).
        len_t = np.int16 if want <= 32767 else np.int32
        lengths = np.zeros(cap, dtype=len_t)
        lengths[:n] = lens
        return [data, lengths] + varrs, ("str", want,
                                         np.dtype(len_t).name, vmode)

    values = np.where(hc.validity, hc.data,
                      np.zeros(1, hc.dtype.np_dtype)) \
        .astype(hc.dtype.np_dtype, copy=False)
    wire = values
    wire_name = hc.dtype.np_dtype.name
    if mode != "plain":
        if hc.dtype.np_dtype == np.float64:
            enc = _encode_float64(values)
            if enc is not None:
                wire, wire_name = enc
        elif hc.dtype.np_dtype.kind == "i":
            narrow = _narrow_int(values, hc.dtype.itemsize)
            if narrow is not None:
                wire = values.astype(narrow)
                wire_name = np.dtype(narrow).name
    # v2: RLE / frame-of-reference / delta compete with the typed wire
    # (and the dictionary below) on wire bytes. All are gathers + exact
    # int arithmetic on the device side.
    best = None                     # (arrays, spec) of the leader
    best_bytes = cap * wire.dtype.itemsize
    if mode == "v2":
        r = _try_rle(wire, n, cap)
        if r is not None and r[2] < best_bytes:
            arrs, (val_name, run_cap), best_bytes = r
            best = (arrs, ("rle", hc.dtype.name, val_name, run_cap, vmode))
        f = _try_for(wire, n, cap)
        if f is not None and f[2] < best_bytes:
            arrs, (off_name,), best_bytes = f
            best = (arrs, ("for", hc.dtype.name, off_name, vmode))
        dl = _try_delta(wire, n, cap)
        if dl is not None and dl[2] < best_bytes:
            arrs, (d_name,), best_bytes = dl
            best = (arrs, ("delta", hc.dtype.name, d_name, vmode))
    if mode != "plain" and wire.dtype.itemsize > 2:
        # Dictionary beats the typed wire only when codes are narrower
        # than the narrowed values (a 0.00..0.10 f64 discount ships int8).
        d = _try_dict(values, n)
        if d is not None:
            codes, uniques = d
            uniques = list(uniques)
            zero = hc.dtype.np_dtype.type(0)
            zero_code = next((i for i, u in enumerate(uniques)
                              if u == zero and not (
                                  isinstance(u, float)
                                  and np.signbit(u))), None)
            if zero_code is None:
                uniques.append(zero)
                zero_code = len(uniques) - 1
            dict_cap = 8
            while dict_cap < len(uniques):
                dict_cap *= 2
            code_t = np.int8 if dict_cap <= 128 else np.int16
            dict_bytes = cap * np.dtype(code_t).itemsize \
                + dict_cap * hc.dtype.itemsize
            ok = np.dtype(code_t).itemsize < wire.dtype.itemsize \
                if mode == "v1" else dict_bytes < best_bytes
            if ok:
                table = np.zeros(dict_cap, dtype=hc.dtype.np_dtype)
                table[:len(uniques)] = uniques
                codes_arr = np.full(cap, zero_code, dtype=code_t)
                codes_arr[:n] = codes
                return [codes_arr, table] + varrs, \
                    ("dnum", hc.dtype.name, np.dtype(code_t).name,
                     dict_cap, vmode)
    if best is not None:
        return best[0] + varrs, best[1]
    data = np.zeros(cap, dtype=wire.dtype)
    data[:n] = wire
    return [data] + varrs, ("num", hc.dtype.name, wire_name, vmode)


# (capacity, specs) -> jitted widen. Filled from whichever thread
# uploads first (concurrent queries / stage threads under the pipelined
# executor), so insertion is double-checked under a lock — two racing
# uploads must share ONE compiled program.
_DECODE_JIT_CACHE: dict = {}
_DECODE_JIT_LOCK = threading.Lock()


def _unpack_validity(bits: jax.Array, cap: int) -> jax.Array:
    """Inverse of np.packbits(bitorder='little'): (cap/8,) uint8 -> bool."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    opened = (bits[:, None] >> shifts[None, :]) & 1
    return opened.reshape(-1)[:cap].astype(jnp.bool_)


def _decode_fn(cap: int, specs: tuple):
    def decode(arrays, num_rows):
        it = iter(arrays)
        row_mask = None
        cols = []

        def valid_of(vmode):
            nonlocal row_mask
            if vmode == "packed":
                return _unpack_validity(next(it), cap)
            if row_mask is None:
                row_mask = jnp.arange(cap, dtype=jnp.int32) < num_rows
            return row_mask

        for spec in specs:
            if spec[0] == "dnum":
                _, logical_name, _code_name, _dict_cap, vmode = spec
                logical = dt.type_named(logical_name)
                codes = next(it).astype(jnp.int32)
                table = next(it)
                data = jnp.take(table, codes, axis=0, mode="clip")
                cols.append(DeviceColumn(logical, data, valid_of(vmode)))
                continue
            if spec[0] == "dstr":
                _, width, _code_name, _dict_cap, vmode = spec
                codes = next(it).astype(jnp.int32)
                table = next(it)
                len_table = next(it).astype(jnp.int32)
                data = jnp.take(table, codes, axis=0, mode="clip")
                lengths = jnp.take(len_table, codes, axis=0, mode="clip")
                cols.append(DeviceColumn(dt.STRING, data, valid_of(vmode),
                                         lengths))
                continue
            if spec[0] == "rle":
                _, logical_name, _val_name, _run_cap, vmode = spec
                logical = dt.type_named(logical_name)
                run_vals = next(it)
                run_ends = next(it)
                rows = jnp.arange(cap, dtype=jnp.int32)
                ridx = jnp.searchsorted(run_ends, rows,
                                        side="right").astype(jnp.int32)
                data = jnp.take(run_vals, ridx, axis=0, mode="clip")
                if data.dtype != logical.np_dtype:
                    data = data.astype(logical.np_dtype)  # pure cast
                # Zero padding rows (a full run table has no zero slot).
                rows_ = jnp.arange(cap, dtype=jnp.int32)
                data = jnp.where(rows_ < num_rows, data,
                                 jnp.zeros_like(data))
                cols.append(DeviceColumn(logical, data, valid_of(vmode)))
                continue
            if spec[0] in ("delta", "for"):
                kind, logical_name, _nname, vmode = spec
                logical = dt.type_named(logical_name)
                base = next(it)            # (1,) int64
                packed_vals = next(it)
                rows = jnp.arange(cap, dtype=jnp.int32)
                off = packed_vals.astype(jnp.int64)
                if kind == "delta":
                    off = jnp.cumsum(off)  # exact int64 (wrap-identical)
                vals = base[0] + off
                vals = jnp.where(rows < num_rows, vals, jnp.int64(0))
                data = vals.astype(logical.np_dtype)       # exact narrow
                cols.append(DeviceColumn(logical, data, valid_of(vmode)))
                continue
            if spec[0] == "str":
                _, width, _len_name, vmode = spec
                data = next(it)
                lengths = next(it).astype(jnp.int32)
                if vmode == "packed":
                    validity = _unpack_validity(next(it), cap)
                else:
                    if row_mask is None:
                        row_mask = jnp.arange(cap, dtype=jnp.int32) \
                            < num_rows
                    validity = row_mask
                cols.append(DeviceColumn(dt.STRING, data, validity,
                                         lengths))
                continue
            _, logical_name, wire_name, vmode = spec
            logical = dt.type_named(logical_name)
            w = next(it)
            if w.dtype == logical.np_dtype:
                data = w
            else:
                data = w.astype(logical.np_dtype)   # pure cast, exact
            if vmode == "packed":
                validity = _unpack_validity(next(it), cap)
            else:
                if row_mask is None:
                    row_mask = jnp.arange(cap, dtype=jnp.int32) < num_rows
                validity = row_mask
            cols.append(DeviceColumn(logical, data, validity))
        return DeviceBatch(tuple(cols), num_rows)
    return decode


def encode_batch(batch, capacity: Optional[int] = None,
                 string_widths: Optional[dict] = None):
    """Host-side half of the upload: analyze + narrow + pad. CPU-only, so
    scan prefetch threads can run it concurrently with device work.
    Returns (arrays, specs, n, cap)."""
    from spark_rapids_tpu.columnar.batch import bucket_capacity
    n = batch.num_rows
    cap = capacity if capacity is not None else bucket_capacity(n)
    assert cap >= n, f"capacity {cap} < rows {n}"
    arrays: List[np.ndarray] = []
    specs = []
    for name, hc in zip(batch.names, batch.columns):
        arrs, spec = encode_column(hc, name, n, cap, string_widths)
        arrays.extend(arrs)
        specs.append(spec)
    arrays.append(np.asarray(n, np.int32))
    return arrays, tuple(specs), n, cap


# ---------------------------------------------------------------------------
# Staging buffer: all of a batch's wire arrays packed into ONE contiguous
# uint8 buffer with a static, 8-byte-aligned offset table derived purely
# from (capacity, specs). The pack half is pure CPU (prefetch threads);
# the upload half hands jax.device_put the buffer's typed views.
# ---------------------------------------------------------------------------

def _align8(off: int) -> int:
    return (off + 7) & ~7


def _column_layout(spec, cap: int) -> List[Tuple[str, Tuple[int, ...]]]:
    """(np dtype name, shape) of every wire array ``spec`` produces, in
    encode order. MUST mirror encode_column exactly — pack_encoded
    asserts each array against this derivation."""
    kind = spec[0]
    if kind == "num":
        _, _logical, wire_name, vmode = spec
        arrs = [(wire_name, (cap,))]
    elif kind == "dnum":
        _, logical, code_name, dict_cap, vmode = spec
        arrs = [(code_name, (cap,)),
                (dt.type_named(logical).np_dtype.name, (dict_cap,))]
    elif kind == "rle":
        _, _logical, val_name, run_cap, vmode = spec
        arrs = [(val_name, (run_cap,)), ("int32", (run_cap,))]
    elif kind in ("delta", "for"):
        _, _logical, nname, vmode = spec
        arrs = [("int64", (1,)), (nname, (cap,))]
    elif kind == "str":
        _, width, len_name, vmode = spec
        arrs = [("uint8", (cap, width)), (len_name, (cap,))]
    elif kind == "dstr":
        _, width, code_name, dict_cap, vmode = spec
        len_name = "int16" if width <= 32767 else "int32"
        arrs = [(code_name, (cap,)), ("uint8", (dict_cap, width)),
                (len_name, (dict_cap,))]
    else:                               # pragma: no cover - spec typo
        raise AssertionError(f"unknown wire spec kind {kind!r}")
    if vmode == "packed":
        arrs.append(("uint8", ((cap + 7) // 8,)))
    return arrs


def _batch_layout(cap: int, specs: tuple):
    """[(offset, np name, shape, nbytes)] for every wire array plus the
    trailing num_rows scalar, with every offset 8-byte aligned, and the
    aligned total staging size."""
    entries = []
    for spec in specs:
        entries.extend(_column_layout(spec, cap))
    entries.append(("int32", ()))          # num_rows scalar
    out = []
    off = 0
    for name, shape in entries:
        count = 1
        for s in shape:
            count *= s
        nbytes = int(np.dtype(name).itemsize * count)
        out.append((off, name, shape, nbytes))
        off = _align8(off + nbytes)
    return out, off


@dataclasses.dataclass
class EncodedBatch:
    """A batch's wire image, packed and ready for one device_put."""

    staging: np.ndarray         # (total,) uint8, offsets 8-byte aligned
    specs: tuple
    n: int
    cap: int

    @property
    def nbytes(self) -> int:
        return self.staging.nbytes


def pack_encoded(arrays, specs, n: int, cap: int) -> EncodedBatch:
    """Pack a batch's wire arrays into one aligned staging buffer. The
    capacity/spec validation happens HERE, once per batch — the upload
    side only dispatches (the per-column re-checks used to run at
    device_put time on the consumer thread)."""
    entries, total = _batch_layout(cap, specs)
    assert len(arrays) == len(entries), \
        f"wire layout mismatch: {len(arrays)} arrays vs " \
        f"{len(entries)} layout entries for specs {specs!r}"
    buf = np.zeros(total, np.uint8)
    for a, (off, name, shape, nbytes) in zip(arrays, entries):
        a = np.asarray(a)               # tobytes() emits C order below
        adt = "bool" if name == "bool" else name
        assert a.dtype == np.dtype(adt) and a.shape == tuple(shape), \
            f"wire array {a.dtype}{a.shape} != layout {name}{shape}"
        # 8-byte alignment is load-bearing: the upload half takes typed
        # numpy views of this buffer at these offsets.
        assert off % 8 == 0, f"staging offset {off} not 8-byte aligned"
        if nbytes:
            buf[off:off + nbytes] = np.frombuffer(a.tobytes(), np.uint8)
    _wrecord("stagingBytes", total)
    _wrecord("stagingBuffers")
    return EncodedBatch(buf, tuple(specs), n, cap)


def pack_batch(batch, capacity: Optional[int] = None,
               string_widths: Optional[dict] = None) -> EncodedBatch:
    """encode + pack: the complete host half of an upload (what pipeline
    prefetch threads stage ahead of the ordered consumer)."""
    from spark_rapids_tpu import monitoring
    with monitoring.span("wire-pack", "host-prefetch",
                         level=monitoring.LEVEL_KERNEL):
        return pack_encoded(*encode_batch(batch, capacity, string_widths))


def _staged_views(enc: EncodedBatch) -> List[np.ndarray]:
    """The staging buffer as its typed wire arrays plus the trailing
    num_rows scalar: zero-copy views (every offset is 8-byte aligned)."""
    entries, _total = _batch_layout(enc.cap, enc.specs)
    return [np.frombuffer(enc.staging,
                          np.bool_ if name == "bool" else np.dtype(name),
                          nbytes // np.dtype(name).itemsize,
                          off).reshape(shape)
            for off, name, shape, nbytes in entries]


def _decode_jit(cap: int, specs: tuple):
    key = ("decode", cap, specs)
    fn = _DECODE_JIT_CACHE.get(key)
    if fn is None:
        with _DECODE_JIT_LOCK:
            fn = _DECODE_JIT_CACHE.get(key)
            if fn is None:
                fn = jax.jit(_decode_fn(cap, specs))
                _DECODE_JIT_CACHE[key] = fn
    return fn


def _decode(enc: EncodedBatch, arrays) -> DeviceBatch:
    out = _decode_jit(enc.cap, enc.specs)(arrays[:-1], arrays[-1])
    out.rows_hint = enc.n
    return out


def upload_packed(enc: EncodedBatch) -> DeviceBatch:
    """Device half: one device_put call over the staging buffer's typed
    views (a transfer per view) + one jitted decode dispatch. The largest
    single allocations in the engine happen here, so the dispatch runs
    under OOM->spill->retry (memory/oom.py)."""
    from spark_rapids_tpu.memory.oom import retry_on_oom
    views = _staged_views(enc)

    def put_and_decode():
        # Injection site INSIDE the retried dispatch: an injected OOM
        # here exercises the same escalation ladder a real allocation
        # failure would (tests/test_chaos.py).
        faults.fault_point("upload")
        return _decode(enc, jax.device_put(views))

    from spark_rapids_tpu import monitoring
    with monitoring.span("upload", "upload",
                         args={"bytes": int(enc.nbytes), "rows": enc.n}):
        out = retry_on_oom(put_and_decode)
    _wrecord("uploadCalls")
    _wrecord("uploadTransfers", len(views))
    _wrecord("uploadedBatches")
    return out


def upload_packed_group(encs: Sequence[EncodedBatch]) -> List[DeviceBatch]:
    """Upload SEVERAL packed batches in one device_put call (the
    tiny-batch coalescing path, wire.minUploadBytes): every member's
    views go out together — still a transfer per view, one call's
    overhead for all — and each member decodes off its own arrays: same
    bytes, same decode program, bit-identical to per-batch uploads."""
    from spark_rapids_tpu.memory.oom import retry_on_oom
    encs = list(encs)
    if not encs:
        return []
    if len(encs) == 1:
        return [upload_packed(encs[0])]
    views = [_staged_views(e) for e in encs]

    def put_all():
        faults.fault_point("upload")
        return jax.device_put(views)

    from spark_rapids_tpu import monitoring
    with monitoring.span("upload-group", "upload",
                         args={"bytes": sum(int(e.nbytes) for e in encs),
                               "batches": len(encs)}):
        staged_all = retry_on_oom(put_all)
    _wrecord("uploadCalls")
    _wrecord("uploadTransfers", sum(len(v) for v in views))
    _wrecord("uploadedBatches", len(encs))
    _wrecord("groupedUploads")
    return [retry_on_oom(_decode, enc, arrays)
            for enc, arrays in zip(encs, staged_all)]


def plan_upload_groups(sizes: Sequence[int],
                       min_bytes: int) -> List[List[int]]:
    """Group consecutive upload indices so members below ``min_bytes``
    share a device_put call: tiny batches accumulate until the group reaches the
    threshold; a batch at/above it always ships alone. Deterministic —
    depends only on the sizes, never on prefetch timing."""
    groups: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    for i, s in enumerate(sizes):
        if s >= min_bytes:
            if cur:
                groups.append(cur)
                cur, cur_bytes = [], 0
            groups.append([i])
            continue
        cur.append(i)
        cur_bytes += s
        if cur_bytes >= min_bytes:
            groups.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        groups.append(cur)
    return groups


def upload_encoded(arrays, specs, n: int, cap: int) -> DeviceBatch:
    """Back-compat device half over unpacked wire arrays: pack + single
    device_put call. Accepts an :class:`EncodedBatch` in the first position
    too (already-packed prefetch payloads)."""
    if isinstance(arrays, EncodedBatch):
        return upload_packed(arrays)
    return upload_packed(pack_encoded(arrays, specs, n, cap))


def upload(batch, capacity: Optional[int] = None,
           string_widths: Optional[dict] = None) -> DeviceBatch:
    """Encode + pack + one device_put call + jitted on-device widen."""
    return upload_packed(pack_batch(batch, capacity, string_widths))
