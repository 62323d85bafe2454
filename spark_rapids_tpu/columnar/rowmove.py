"""Packed row movement: gather/compact/concat/split over packed slabs.

TPU-first redesign of the engine's row-movement primitives (the cuDF
``Table.gather`` / ``contiguous_split`` analogs the reference reaches via
JNI — GpuColumnVector.java from(Table), GpuCoalesceBatches.scala:643).

Two measured facts of the target chip shape this module (one TPU v5e,
``scripts/chip_probe.py rowmove``; my chip runs, PR 30):

1. Row movement is bound by ROW OPERATIONS, not bytes, so all columns of a
   batch are packed into a few "slabs" and moved together:
   - ``w0``, ``w1``, ...: uint32 WORDS, at most ``WORDS_PER_SLAB`` (16) a
     row each — every value 4 bytes or narrower as one word, int64 and
     timestamp as two (split by shifts: the TPU's emulated 64-bit ints have
     no bitcast), string bytes four to a word plus a length word, and bool
     data and ALL validity vectors as flag bits, 32 to a word;
   - ``f64``: float64 columns stacked (N, k) — the emulated f64 has no bit
     access at all, so these stay in the float domain.
   Why words: one gather of ``u32[1572864, w]`` costs 8.5-10.5 ms for any
   w <= 16 (85 ms at w = 32), where ``u8[N, 27..64]`` costs 18 ms and
   ``i64[N, 4]`` 17 ms (XLA moves its hi and lo halves apart). Q5's big
   mesh shards (59 bytes + 11 flags a row) were three slabs, ``u8[N,27]``,
   ``i64[N,4]``, ``f64[N,2]``, and compacted in 166 ms by gather; as one
   14-word slab and ``f64[N,2]`` they take 38 ms. (What it costs to
   compile: the string bytes' u8 <-> u32 bitcast makes a program that
   gathers a string column at ~1M rows compile 3-5 times slower; the flag
   bits cost nothing that shows — PERF.md section 6, PR 30.)
2. A slab GATHER is cheaper than a slab SCATTER: ``zeros.at[pos].set(slab,
   mode="drop")`` over those three slabs took 305 ms for 1,572,864 rows
   (~100 ms a slab whatever its width; ``unique_indices`` changes nothing).
   So a row is moved by ONE 1-D int32 index scatter that says which source
   row lands in which output slot (``_live_sources``) and one gather per
   slab — in ``compact_batch``, ``concat_compact`` and ``split_batch``
   alike; no mover scatters a slab.

Unpacking is pure bitcasts/shifts/slices that XLA fuses into the consumer.

Null/data discipline: moved rows whose destination is dead are zeroed whole
(one ``where`` per slab), preserving the engine's deterministic-padding
invariant. Values at rows whose validity is False are NOT otherwise
normalized here — consumers must mask by validity (they all do; the
fingerprint kernel normalizes null key data itself).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar.batch import DeviceBatch, DeviceColumn


# A word slab holds at most this many uint32 words a row: the width up to
# which one gather's cost stays flat on the chip (scripts/chip_probe.py
# rowmove: u32[1572864, w] gathers in 8.5-10.5 ms for w <= 16, 85 ms at 32).
WORDS_PER_SLAB = 16
_F64 = "f64"


def _bits_to_words(bits: List[jax.Array]) -> List[jax.Array]:
    """(N,) bools -> (N,) uint32 words of 32 flags each."""
    out = []
    for lo in range(0, len(bits), 32):
        word = jnp.zeros(bits[0].shape, jnp.uint32)
        for j, b in enumerate(bits[lo:lo + 32]):
            word = word | (b.astype(jnp.uint32) << j)
        out.append(word)
    return out


def _to_words(arr: jax.Array) -> List[jax.Array]:
    """A non-bool, non-float64 column's data as (N,) / (N, k) uint32."""
    dt = arr.dtype
    if dt == jnp.int64:
        # The TPU's emulated 64-bit ints have no bitcast: split by
        # arithmetic (both halves are exact, the narrowing wraps).
        return [(arr >> 32).astype(jnp.uint32), arr.astype(jnp.uint32)]
    if arr.ndim == 2:                        # string bytes (N, width)
        n, w = arr.shape
        if w % 4:
            arr = jnp.pad(arr, ((0, 0), (0, 4 - w % 4)))
        return [jax.lax.bitcast_convert_type(
            arr.reshape(n, -1, 4), jnp.uint32)]
    if dt.itemsize == 4:
        return [jax.lax.bitcast_convert_type(arr, jnp.uint32)]
    unsigned = jnp.uint8 if dt.itemsize == 1 else jnp.uint16
    return [jax.lax.bitcast_convert_type(arr, unsigned).astype(jnp.uint32)]


def _from_words(words: jax.Array, np_dtype, width: int = 0) -> jax.Array:
    """Inverse of ``_to_words`` over the (N, k) words it produced
    (``width``: a string column's byte width)."""
    np_dtype = np.dtype(np_dtype)
    if width:
        n = words.shape[0]
        return jax.lax.bitcast_convert_type(
            words, jnp.uint8).reshape(n, -1)[:, :width]
    if np_dtype == np.int64:
        return ((words[:, 0].astype(jnp.int64) << 32)
                | words[:, 1].astype(jnp.int64))
    if np_dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(words[:, 0], jnp.dtype(np_dtype))
    unsigned = jnp.uint8 if np_dtype.itemsize == 1 else jnp.uint16
    return jax.lax.bitcast_convert_type(
        words[:, 0].astype(unsigned), jnp.dtype(np_dtype))


def _word_count(c: DeviceColumn) -> int:
    """uint32 words a row of the column's data (and string lengths) takes."""
    if c.dtype.is_string:
        return -(-c.string_width // 4) + 1
    if c.dtype.np_dtype == np.float64 or c.dtype.np_dtype == np.bool_:
        return 0
    return 2 if c.dtype.np_dtype == np.int64 else 1


def pack_batch(batch: DeviceBatch) -> Dict[str, jax.Array]:
    """Pack all columns (+ validities, string lengths) into slabs:
    ``f64`` and as many word slabs ``w0``, ``w1``, ... as the row needs."""
    words: List[jax.Array] = []
    bits: List[jax.Array] = []
    f64: List[jax.Array] = []
    for c in batch.columns:
        if c.dtype.np_dtype == np.float64:
            f64.append(c.data)
        elif c.dtype.np_dtype == np.bool_:
            bits.append(c.data)
        else:
            words += _to_words(c.data)
            if c.dtype.is_string:
                words += _to_words(c.lengths)
        bits.append(c.validity)
    words += _bits_to_words(bits)
    slabs: Dict[str, jax.Array] = {}
    if words:
        row = jnp.concatenate(
            [w if w.ndim == 2 else w[:, None] for w in words], axis=1)
        for i, lo in enumerate(range(0, row.shape[1], WORDS_PER_SLAB)):
            slabs[f"w{i}"] = row[:, lo:lo + WORDS_PER_SLAB]
    if f64:
        slabs[_F64] = jnp.stack(f64, axis=1)
    return slabs


def unpack_batch(slabs: Dict[str, jax.Array], template: DeviceBatch,
                 num_rows: jax.Array,
                 sel: Optional[jax.Array] = None) -> DeviceBatch:
    """Rebuild a DeviceBatch from slabs, using ``template`` for the schema
    (dtypes + string widths)."""
    f64 = slabs.get(_F64)
    chunks = [slabs[k] for k in sorted(
        (k for k in slabs if k != _F64), key=lambda k: int(k[1:]))]
    row = jnp.concatenate(chunks, axis=1) if chunks else None
    off = 0                                         # next data word
    flags = sum(_word_count(c) for c in template.columns)   # first flag word
    bit = 0                                         # next flag

    def flag() -> jax.Array:
        nonlocal bit
        out = ((row[:, flags + bit // 32] >> (bit % 32)) & 1) != 0
        bit += 1
        return out

    cols: List[DeviceColumn] = []
    f64_i = 0
    for c in template.columns:
        k = _word_count(c)
        lengths = None
        if c.dtype.is_string:
            data = _from_words(row[:, off:off + k - 1], np.uint8,
                               c.string_width)
            lengths = _from_words(row[:, off + k - 1:off + k], np.int32)
        elif c.dtype.np_dtype == np.float64:
            data = f64[:, f64_i]
            f64_i += 1
        elif c.dtype.np_dtype == np.bool_:
            data = flag()
        else:
            data = _from_words(row[:, off:off + k], c.dtype.np_dtype)
        off += k
        cols.append(DeviceColumn(c.dtype, data, flag(), lengths))
    return DeviceBatch(tuple(cols), jnp.asarray(num_rows, jnp.int32),
                       sel=sel)


def _take_slabs(slabs: Dict[str, jax.Array], indices: jax.Array,
                valid_dst: jax.Array) -> Dict[str, jax.Array]:
    """One gather per slab; dead destination slots are zeroed whole."""
    out = {}
    for k, slab in slabs.items():
        g = jnp.take(slab, indices, axis=0, mode="clip")
        out[k] = jnp.where(valid_dst[:, None], g, jnp.zeros_like(g))
    return out


def gather_rows(batch: DeviceBatch, indices: jax.Array,
                new_num_rows: jax.Array,
                valid_dst: Optional[jax.Array] = None) -> DeviceBatch:
    """Take rows at ``indices`` into a dense batch of ``len(indices)``
    capacity. ``valid_dst`` masks live destination slots (defaults to
    ``arange < new_num_rows``); dead slots are zeroed whole."""
    cap = indices.shape[0]
    if valid_dst is None:
        valid_dst = jnp.arange(cap, dtype=jnp.int32) < new_num_rows
    out = _take_slabs(pack_batch(batch), indices, valid_dst)
    return unpack_batch(out, batch, new_num_rows)


def take_columns(columns: Sequence[jax.Array],
                 indices: jax.Array) -> List[jax.Array]:
    """``[c[indices] for c in columns]`` for (N,) integer and float64
    columns, in ONE gather per slab: every integer column as uint32 words
    side by side (``WORDS_PER_SLAB`` a slab), the float64 columns stacked
    (N, k). Taken one by one, each column is a 1-D gather that costs the
    chip as much as a whole slab, and a 64-bit one is two (fact 1 above;
    PERF.md, PR 34). ``indices`` must be in range."""
    parts = [None if c.dtype == jnp.float64 else _to_words(c)
             for c in columns]
    words = [w for p in parts if p is not None for w in p]
    f64 = [c for c, p in zip(columns, parts) if p is None]
    if words:
        row = jnp.stack(words, axis=1)
        row = jnp.concatenate(
            [jnp.take(row[:, lo:lo + WORDS_PER_SLAB], indices, axis=0,
                      mode="clip")
             for lo in range(0, row.shape[1], WORDS_PER_SLAB)], axis=1)
    if f64:
        taken = jnp.take(jnp.stack(f64, axis=1), indices, axis=0,
                         mode="clip")
    out, off, f64_i = [], 0, 0
    for c, p in zip(columns, parts):
        if p is None:
            out.append(taken[:, f64_i])
            f64_i += 1
        else:
            out.append(_from_words(row[:, off:off + len(p)], c.dtype))
            off += len(p)
    return out


def _live_sources(live: jax.Array, capacity: int) -> jax.Array:
    """``(capacity,)`` int32: slot r holds the row id of the r-th live row
    (stable order); slots past the live count hold 0 and are masked by the
    gather's ``valid_dst``. ONE 1-D int32 scatter — the only scatter a
    mover issues. Live rows past ``capacity`` are dropped."""
    rank = jnp.cumsum(live.astype(jnp.int32)) - 1
    return jnp.zeros((capacity,), jnp.int32).at[
        jnp.where(live, rank, capacity)].set(
        jnp.arange(live.shape[0], dtype=jnp.int32), mode="drop")


def compact_batch(batch: DeviceBatch, keep: Optional[jax.Array] = None,
                  capacity: Optional[int] = None) -> DeviceBatch:
    """Materialize live rows (optionally ANDed with ``keep``) as a packed
    prefix — the selection-vector discharge point. ``capacity`` is the
    static output capacity (default: the batch's own; a smaller one
    requires ``live rows <= capacity``, ``shrink_to_capacity``'s case).

    The index scatter builds the live-row list, then one packed gather per
    slab at the output capacity moves the data — cost scales with the
    OUTPUT rows, so shrinking a mostly-dead batch is nearly free."""
    live = batch.row_mask() if keep is None else (keep & batch.row_mask())
    if capacity is None:
        capacity = batch.capacity
    return gather_rows(batch, _live_sources(live, capacity),
                       jnp.sum(live.astype(jnp.int32)))


def _widen_strings(batches: Sequence[DeviceBatch]) -> List[DeviceBatch]:
    """Re-pad every string column to its widest member so slabs line up."""
    from spark_rapids_tpu.columnar.batch import string_repad
    widths = [max(b.columns[ci].string_width for b in batches)
              if c.dtype.is_string else None
              for ci, c in enumerate(batches[0].columns)]
    return [DeviceBatch(tuple(string_repad(c, w) if w is not None else c
                              for c, w in zip(b.columns, widths)),
                        b.num_rows, sel=b.sel) for b in batches]


def concat_compact(batches: Sequence[DeviceBatch],
                   capacity: int) -> DeviceBatch:
    """Concatenate the LIVE rows of ``batches`` into one dense batch.

    Selection-vector aware: the members' slabs are laid end to end (a copy
    at memory bandwidth), so a member's row id is its offset + row and the
    running live total falls out of ONE cumsum over the joined live masks;
    then the compaction's index scatter and one gather per slab."""
    assert batches, "concat of zero batches"
    batches = _widen_strings(batches)
    packed = [pack_batch(b) for b in batches]
    joined = {k: jnp.concatenate([p[k] for p in packed], axis=0)
              for k in packed[0]}
    live = jnp.concatenate([b.row_mask() for b in batches])
    total = jnp.sum(live.astype(jnp.int32))
    out = _take_slabs(joined, _live_sources(live, capacity),
                      jnp.arange(capacity, dtype=jnp.int32) < total)
    return unpack_batch(out, batches[0], total)


def concat_stacked(stacked: DeviceBatch, capacity: int) -> DeviceBatch:
    """``concat_compact`` of the members of a STACKED batch — every leaf
    with a leading member axis, ``num_rows`` of shape (n,), as a
    collective hands it back. The members already lie end to end, so the
    join is a reshape."""
    n = stacked.num_rows.shape[0]
    flat = jax.tree_util.tree_map(
        lambda x: x.reshape((-1,) + x.shape[2:]), stacked.columns)
    rows = flat[0].capacity // n
    live = (jnp.arange(rows, dtype=jnp.int32)[None, :]
            < stacked.num_rows[:, None])
    if stacked.sel is not None:
        live = live & stacked.sel
    joined = DeviceBatch(flat, jnp.asarray(n * rows, jnp.int32))
    return compact_batch(joined, live.reshape(-1), capacity)


def split_batch(batch: DeviceBatch, pids: jax.Array, num_partitions: int,
                piece_capacity: Optional[int] = None) -> DeviceBatch:
    """Pack each destination's live rows into its own piece (stable
    order), ALL PIECES IN ONE PASS: rank each row within its destination
    (one masked cumsum per destination), scatter its row id to slot
    ``pid * piece_capacity + rank`` of one int32 array, gather each slab
    once. Returns the pieces STACKED — leaves ``(num_partitions,
    piece_capacity, ...)``, ``num_rows`` of shape (num_partitions,) — which
    is the operand ``jax.lax.all_to_all`` wants. A row moves once;
    the gather touches num_partitions x piece_capacity output rows.

    ``piece_capacity`` None means the batch's capacity (no piece can
    overflow); a smaller one truncates a piece to its first
    ``piece_capacity`` rows and ``num_rows`` to match."""
    n = num_partitions
    pc = batch.capacity if piece_capacity is None else piece_capacity
    live = batch.row_mask()
    slot = jnp.full(live.shape, n * pc, jnp.int32)
    counts = []
    for p in range(n):
        mine = live & (pids == p)
        rank = jnp.cumsum(mine.astype(jnp.int32)) - 1
        slot = jnp.where(mine & (rank < pc), p * pc + rank, slot)
        counts.append(jnp.sum(mine.astype(jnp.int32)))
    num_rows = jnp.minimum(jnp.stack(counts), pc)
    idx = jnp.zeros((n * pc,), jnp.int32).at[slot].set(
        jnp.arange(live.shape[0], dtype=jnp.int32), mode="drop")
    valid_dst = (jnp.arange(pc, dtype=jnp.int32)[None, :]
                 < num_rows[:, None]).reshape(-1)
    out = _take_slabs(pack_batch(batch), idx, valid_dst)
    flat = unpack_batch(out, batch, num_rows)
    return DeviceBatch(jax.tree_util.tree_map(
        lambda x: x.reshape((n, pc) + x.shape[1:]), flat.columns), num_rows)
