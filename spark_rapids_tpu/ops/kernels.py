"""Shared device kernels: key normalization, lexicographic sort, grouping.

These are the TPU-first replacements for the cuDF kernels the reference
reaches through JNI (Table.orderBy, Table.groupBy, hash partition): everything
is expressed as stable sorts, segmented reductions and scatters over
fixed-capacity arrays, so XLA can fuse and tile them (no dynamic allocations,
no data-dependent shapes — SURVEY.md §7 "hard parts" #1/#3).

Key ideas:
- ``sort_key_passes`` turns any key column into a list of uint32 radix words,
  most-significant first, already adjusted for asc/desc and null ordering.
  A multi-column sort is then a sequence of stable sorts over the reversed
  pass list (LSD radix over words, ``radix_sort``).
- ``group_ids`` gives each live row a dense group index by sorting rows by a
  128-bit key fingerprint (two independent murmur3 streams + null pattern);
  equal keys become adjacent, segment boundaries fall where the fingerprint
  changes. Collision probability is ~n^2/2^64 per batch — the same class of
  trade cuDF's hash aggregation makes.
- ``segment_reduce`` wraps jax.ops.segment_* with null discipline (Spark
  semantics: aggregates skip nulls; all-null groups yield null).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import DeviceBatch, DeviceColumn
from spark_rapids_tpu.columnar.rowmove import take_columns
from spark_rapids_tpu.exprs import hash as mh


# ---------------------------------------------------------------------------
# Orderable key normalization
# ---------------------------------------------------------------------------

def _orderable_u32_words(col: DeviceColumn) -> List[jnp.ndarray]:
    """Column -> list of uint32 words, most-significant first, such that
    lexicographic unsigned comparison of the word tuple == SQL ordering
    (ascending, nulls handled separately)."""
    t = col.dtype
    if t.is_string:
        # Bytes are already unsigned-lexicographic; zero padding sorts
        # shorter strings first, matching SQL byte ordering (strings with
        # embedded NUL bytes are the known approximation).
        data = col.data
        w = data.shape[1]
        words = []
        for i in range(0, w, 4):
            chunk = data[:, i:i + 4]
            if chunk.shape[1] < 4:
                pad = jnp.zeros((data.shape[0], 4 - chunk.shape[1]),
                                jnp.uint8)
                chunk = jnp.concatenate([chunk, pad], axis=1)
            word = (chunk[:, 0].astype(jnp.uint32) << 24) | \
                   (chunk[:, 1].astype(jnp.uint32) << 16) | \
                   (chunk[:, 2].astype(jnp.uint32) << 8) | \
                   chunk[:, 3].astype(jnp.uint32)
            words.append(word)
        return words
    if t.is_floating:
        if t.name == "float32":
            bits = jnp.asarray(col.data, jnp.float32).view(jnp.uint32)
            # IEEE total order: flip all bits if negative else flip sign.
            neg = (bits >> jnp.uint32(31)) == 1
            bits = jnp.where(neg, ~bits, bits | jnp.uint32(0x80000000))
            # Spark: NaN sorts greater than everything; canonical NaN bits
            # already sort above +inf after the transform.
            return [bits]
        # float64: TPU's x64 emulation has no 64-bit bitcast, so the key
        # stays in the FLOAT domain (argsort compares f64 directly):
        #   [nan tier (u32), value (f64, NaNs zeroed), -0/+0 tiebreak].
        x = jnp.asarray(col.data, jnp.float64)
        nan = jnp.isnan(x)
        nan_word = nan.astype(jnp.uint32)           # NaN sorts greatest
        val = jnp.where(nan, jnp.float64(0.0), x)
        negzero = (x == 0.0) & (1.0 / x < 0)
        zero_word = jnp.where(x == 0.0,
                              jnp.where(negzero, jnp.uint32(0),
                                        jnp.uint32(1)),
                              jnp.uint32(0))        # -0.0 before +0.0
        return [nan_word, val, zero_word]
    if t.name in ("int64", "timestamp"):
        u = col.data.astype(jnp.int64).astype(jnp.uint64) ^ \
            jnp.uint64(0x8000000000000000)
        return [(u >> jnp.uint64(32)).astype(jnp.uint32),
                (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)]
    # bool/int8/16/32/date -> one word, sign-bias flip.
    u = col.data.astype(jnp.int32).astype(jnp.uint32) ^ jnp.uint32(0x80000000)
    return [u]


def sort_key_passes(col: DeviceColumn, ascending: bool,
                    nulls_first: bool) -> List[jnp.ndarray]:
    """Radix word passes for one sort key, MSW first, including the null
    ordering word. Descending keys get bit-flipped words."""
    words = _orderable_u32_words(col)
    if not ascending:
        # u32 words flip bitwise; float-domain passes flip by negation.
        words = [jnp.negative(w) if jnp.issubdtype(w.dtype, jnp.floating)
                 else ~w for w in words]
    # Null word: 0 sorts first. nulls_first -> nulls get 0, else 1-flip.
    if nulls_first:
        null_word = jnp.where(col.validity, jnp.uint32(1), jnp.uint32(0))
    else:
        null_word = jnp.where(col.validity, jnp.uint32(0), jnp.uint32(1))
    # Zero data words for nulls so null ordering is decided by null_word.
    words = [jnp.where(col.validity, w, jnp.zeros_like(w)) for w in words]
    return [null_word] + words


# The most passes whose words ride one another's sorts (``radix_sort``): a
# longer sort goes in chunks. A sort's compile time is its operands — ~12 s
# each over every distinct sort from 32,768 rows up, whatever the size, for
# a described v5e in the sandbox — and its run time hardly: 2.4 / 3.2 / 5.0
# ms with 2 / 4 / 8 operands at 786,432 rows against 5.5 for one packed
# gather (my chip run, PR 34). With all seven passes of q67's window frame
# riding one another the frame ran in 25.0 ms and compiled in 253 s, where
# the loop before it took 169.9 ms and 112.5 s; chunks of four keep
# every sort at seven operands.
_RIDE_PASSES = 4


def radix_sort(passes: Sequence[jnp.ndarray], capacity: int,
               unstable_first: bool = False
               ) -> Tuple[jnp.ndarray, List[jnp.ndarray]]:
    """Stable LSD radix sort: the ONE traced implementation every
    multi-pass sort in this engine shares (full sorts, grouping, the
    window's frame, per-group string min/max — and through the kernel
    cache, the fused paths).

    ``passes`` are per-row word arrays, most significant first. Returns
    ``(perm, sorted_passes)``: the permutation that orders rows by the
    lexicographic pass tuple, ties in original row order, and every pass
    in that order (``sorted_passes[i] == passes[i][perm]``).
    ``unstable_first`` relaxes tie order on the least-significant pass
    only (spark.rapids.sql.stableSort.enabled off) — every later pass
    must stay stable for multi-key correctness.

    No column is gathered by itself. Each pass is one ``lax.sort`` keyed
    by its word in the order the passes so far left, with the other words
    of its chunk and the permutation riding as operands: on the chip a
    1-D gather of a batch's length costs 5.6-7.6 ms at 786,432 rows, as
    much as a packed 16-word row gather and twice a four-operand sort
    (PERF.md, PR 34), and the loop this replaces paid two a pass and one
    more a word its caller read back. More than ``_RIDE_PASSES`` passes
    go in chunks, least significant first: a chunk's words enter it
    through ONE packed gather by the permutation so far
    (``rowmove.take_columns``) and ride only their own chunk's sorts — but
    a float64 word, which has no slab to share, rides on through the
    later chunks'. The sorted words of the last chunk and the float64
    ones are the sorts' outputs, the others' one more packed gather; what
    the caller does not read XLA drops, with the operand that carried it.

    The riders keep one operand order whatever the key, so the passes
    of a chunk that share a key dtype are ONE sort to the compiler."""
    perm, words = _radix_chunks(passes, capacity, unstable_first)
    stale = [i for i in range(len(passes)) if i not in words]
    if stale:
        words.update(zip(stale, take_columns([passes[i] for i in stale],
                                             perm)))
    return perm, [words[i] for i in range(len(passes))]


def _radix_chunks(passes: Sequence[jnp.ndarray], capacity: int,
                  unstable_first: bool) -> Tuple[jnp.ndarray, dict]:
    """``radix_sort``'s passes: the permutation, and by their index in
    ``passes`` the words that left the last sort in sorted order."""
    k = len(passes)
    n_chunks = max(-(-k // _RIDE_PASSES), 1)
    # as few chunks as ``_RIDE_PASSES`` allows, their sizes one apart at most
    bounds = [(c * k) // n_chunks for c in range(n_chunks + 1)]
    perm = jnp.arange(capacity, dtype=jnp.int32)
    words: dict = {}
    for lo, hi in reversed(list(zip(bounds, bounds[1:]))):  # LSD first
        chunk = list(range(lo, hi))
        # Float64 words of the chunks before ride on: uint32 riders first,
        # then float64 ones, one order for every key.
        words = {j: w for j, w in words.items() if w.dtype == jnp.float64}
        riders = sorted(chunk + list(words),
                        key=lambda j: (passes[j].dtype == jnp.float64, j))
        words.update(zip(chunk, [passes[i] for i in chunk] if hi == k
                         else take_columns([passes[i] for i in chunk],
                                           perm)))
        for i in reversed(chunk):
            rest = [j for j in riders if j != i]
            out = jax.lax.sort(
                [words[i]] + [words[j] for j in rest] + [perm], num_keys=1,
                is_stable=not (unstable_first and i == k - 1))
            words.update(zip([i] + rest, out[:-1]))
            perm = out[-1]
    return perm, words


def lex_sort_perm(passes: List[jnp.ndarray], live: jnp.ndarray,
                  capacity: int, stable: bool = True) -> jnp.ndarray:
    """Permutation sorting rows by the MSW-first word passes; dead rows
    (padding / deselected) always sort last. ``live`` is either a
    (capacity,) bool mask (row_mask) or an int32 row-count scalar."""
    if getattr(live, "ndim", 0) == 0 or np.isscalar(live):
        live = jnp.arange(capacity, dtype=jnp.int32) < live
    # Padding pass first (most significant of all): dead rows sort last.
    pad_last = jnp.where(live, jnp.uint32(0), jnp.uint32(0xFFFFFFFF))
    return _radix_chunks([pad_last] + list(passes), capacity,
                         unstable_first=not stable)[0]


# ---------------------------------------------------------------------------
# Grouping
# ---------------------------------------------------------------------------

_SEED_A = 42
_SEED_B = 0x5EED


def key_fingerprint(cols: Sequence[DeviceColumn],
                    capacity: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Two independent 32-bit fingerprints of the key tuple per row.

    Null rows must differ from any value: the null pattern is mixed into the
    second stream explicitly (murmur3 passes the seed through on null, which
    would otherwise let NULL collide with unlucky values)."""
    ha = jnp.full((capacity,), np.uint32(_SEED_A), dtype=jnp.uint32)
    hb = jnp.full((capacity,), np.uint32(_SEED_B), dtype=jnp.uint32)
    for i, c in enumerate(cols):
        # Null cells may carry arbitrary data (packed row movement does not
        # zero them — rowmove.py contract); normalize so all NULLs
        # fingerprint identically. The null flag itself is mixed below.
        if c.dtype.is_string:
            data = jnp.where(c.validity[:, None], c.data,
                             jnp.zeros_like(c.data))
            lens = jnp.where(c.validity, c.lengths, 0)
            c = DeviceColumn(c.dtype, data, c.validity, lens)
        elif c.dtype.is_floating:
            # Grouping equality: -0.0 == 0.0 and NaN == NaN (Spark inserts
            # NormalizeNaNAndZero before grouping; we fold it in here).
            data = jnp.where(c.data == 0, jnp.zeros_like(c.data), c.data)
            data = jnp.where(c.validity, data, jnp.zeros_like(data))
            c = DeviceColumn(c.dtype, data, c.validity)
        else:
            data = jnp.where(c.validity, c.data, jnp.zeros_like(c.data))
            c = DeviceColumn(c.dtype, data, c.validity)
        ha = mh.hash_column(jnp, c, c.dtype, ha)
        hb = mh.hash_column(jnp, c, c.dtype, hb)
        # Mix null flag into stream B so NULL != seed-collision value.
        nullbit = jnp.where(c.validity, jnp.uint32(0),
                            jnp.uint32(0x9E3779B9 + i))
        hb = mh._fmix(jnp, hb ^ nullbit, 4)
    return ha, hb


@dataclasses.dataclass
class Grouping:
    """Result of group_ids: rows sorted so equal keys are adjacent."""

    perm: jnp.ndarray         # (capacity,) row permutation (padding last)
    group_of_sorted: jnp.ndarray  # (capacity,) dense group id per sorted row
    num_groups: jnp.ndarray   # int32 scalar
    group_leader: jnp.ndarray  # (capacity,) original row index of each
    #                            group's first sorted row (by group id)


def smallest_fingerprints(ha: jnp.ndarray, hb: jnp.ndarray,
                          live: jnp.ndarray, limit: int):
    """The distinct ``(ha, hb)`` pairs among live rows in ascending order,
    as far as the first ``limit + 1``: ``(pa, pb, found)`` with ``pa`` and
    ``pb`` of ``limit + 1`` entries and ``found`` <= ``limit + 1`` of them
    set. ``found == limit + 1`` says the batch holds MORE than ``limit``
    groups. Each round is two masked min-reductions ("the smallest pair
    above the last one found") and the loop ends with the batch's last
    pair: a batch of four groups pays five rounds, one of thousands
    ``limit + 1``. No sort, nothing moved."""
    top = jnp.uint32(0xFFFFFFFF)

    def wanted(state):
        found, more = state[0], state[1]
        return more & (found <= limit)

    def next_pair(state):
        found, _, la, lb, pa, pb = state
        above = (found == 0) | (ha > la) | ((ha == la) & (hb > lb))
        cand = live & above
        a = jnp.min(jnp.where(cand, ha, top))
        b = jnp.min(jnp.where(cand & (ha == a), hb, top))
        more = jnp.any(cand)
        # A round that finds nothing writes past the pairs found: unread.
        pa = jax.lax.dynamic_update_index_in_dim(pa, a, found, 0)
        pb = jax.lax.dynamic_update_index_in_dim(pb, b, found, 0)
        return found + more.astype(jnp.int32), more, a, b, pa, pb

    none = jnp.zeros((limit + 1,), jnp.uint32)
    found, _, _, _, pa, pb = jax.lax.while_loop(
        wanted, next_pair,
        (jnp.int32(0), jnp.bool_(True), jnp.uint32(0), jnp.uint32(0),
         none, none))
    return pa, pb, found


def group_ids(batch: DeviceBatch, key_ordinals: Sequence[int],
              fingerprints: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None
              ) -> Grouping:
    """Assign dense group ids over the key columns (cuDF groupBy analog).
    ``fingerprints``: the keys' ``key_fingerprint``, where the caller has
    it already."""
    cap = batch.capacity
    if fingerprints is None:
        cols = [batch.columns[i] for i in key_ordinals]
        fingerprints = key_fingerprint(cols, cap)
    ha, hb = fingerprints
    live = batch.row_mask()
    # Sort rows by (live desc, ha, hb): padding last.
    dead = jnp.where(live, jnp.uint32(0), jnp.uint32(0xFFFFFFFF))
    perm, (sdead, sa, sb) = radix_sort([dead, ha, hb], cap)
    slive = sdead == 0
    prev_a = jnp.concatenate([sa[:1] ^ jnp.uint32(1), sa[:-1]])
    prev_b = jnp.concatenate([sb[:1], sb[:-1]])
    new_seg = ((sa != prev_a) | (sb != prev_b)) & slive
    # First live sorted row always starts a segment.
    first_live = jnp.argmax(slive.astype(jnp.int32))
    new_seg = new_seg | (jnp.arange(cap) == first_live) & slive
    gid = jnp.cumsum(new_seg.astype(jnp.int32)) - 1
    # Padding rows go to the last slot (their writes are masked downstream).
    gid = jnp.where(slive, gid, jnp.int32(max(cap - 1, 0)))
    num_groups = jnp.sum(new_seg, dtype=jnp.int32)
    # Leader: original row index of each group's first sorted row.
    leader = jnp.zeros((cap,), jnp.int32).at[
        jnp.where(new_seg, gid, cap)].set(perm, mode="drop")
    return Grouping(perm, gid, num_groups, leader)


def segment_reduce(values: jnp.ndarray, validity: jnp.ndarray,
                   gid: jnp.ndarray, capacity: int, kind: str,
                   count_also: bool = False):
    """Segmented aggregate with Spark null discipline.

    values/validity are already permuted to sorted order; gid is
    group_of_sorted. Returns (agg (capacity,), non_null_count (capacity,)).
    ``kind``: sum | min | max.
    """
    if kind == "sum":
        masked = jnp.where(validity, values,
                           jnp.zeros_like(values))
        agg = jax.ops.segment_sum(masked, gid, num_segments=capacity)
    elif kind in ("min", "max"):
        if jnp.issubdtype(values.dtype, jnp.floating):
            # Spark orders NaN greatest. Reduce in the float domain with
            # NaNs masked out (bitcast-free — TPU's x64 emulation cannot
            # bitcast f64): min ignores NaN unless the group is all-NaN;
            # max is NaN whenever any valid NaN exists.
            isnan = jnp.isnan(values)
            real = validity & ~isnan
            nanv = jnp.asarray(jnp.nan, values.dtype)
            if kind == "min":
                masked = jnp.where(real, values,
                                   jnp.asarray(jnp.inf, values.dtype))
                m = jax.ops.segment_min(masked, gid, num_segments=capacity)
                has_real = jax.ops.segment_sum(
                    real.astype(jnp.int32), gid, num_segments=capacity) > 0
                agg = jnp.where(has_real, m, nanv)
            else:
                masked = jnp.where(real, values,
                                   jnp.asarray(-jnp.inf, values.dtype))
                m = jax.ops.segment_max(masked, gid, num_segments=capacity)
                has_nan = jax.ops.segment_sum(
                    (validity & isnan).astype(jnp.int32), gid,
                    num_segments=capacity) > 0
                agg = jnp.where(has_nan, nanv, m)
        else:
            masked = jnp.where(validity, values,
                               _identity_for(values.dtype, kind))
            red = jax.ops.segment_min if kind == "min" \
                else jax.ops.segment_max
            agg = red(masked, gid, num_segments=capacity)
    else:
        raise ValueError(kind)
    counts = jax.ops.segment_sum(validity.astype(jnp.int64), gid,
                                 num_segments=capacity)
    return agg, counts


def segment_minmax_string(data: jnp.ndarray, lengths: jnp.ndarray,
                          validity: jnp.ndarray, gid: jnp.ndarray,
                          capacity: int, want_max: bool):
    """Per-group lexicographic min/max of a string column.

    Inputs are in group-sorted order (groups adjacent). Strategy: one more
    stable radix sort keyed by [gid, null-loses, value words] — after it the
    first row of each gid run is the winner. Returns a (data, validity,
    lengths) buffer triple indexed by group id.
    """
    col = DeviceColumn(dt.STRING, data, validity, lengths)
    words = _orderable_u32_words(col)
    if want_max:
        words = [~w for w in words]
        # Max must also prefer longer strings on equal prefix: flip the
        # length tiebreak too (zero padding already makes shorter sort
        # first ascending; flipping words flips prefix order but not the
        # implicit length order, so add an explicit length word).
        lenword = ~lengths.astype(jnp.uint32)
    else:
        lenword = lengths.astype(jnp.uint32)
    loser = jnp.where(validity, jnp.uint32(0), jnp.uint32(0xFFFFFFFF))
    words = [jnp.where(validity, w, jnp.uint32(0)) for w in words]
    lenword = jnp.where(validity, lenword, jnp.uint32(0))
    passes = [gid.astype(jnp.uint32), loser] + words + [lenword]
    perm, words = _radix_chunks(passes, capacity, unstable_first=False)
    sorted_gid = words[0].astype(gid.dtype)
    prev = jnp.concatenate([sorted_gid[:1] ^ 1, sorted_gid[:-1]])
    new_seg = sorted_gid != prev
    new_seg = new_seg | (jnp.arange(capacity) == 0)
    winner = jnp.zeros((capacity,), jnp.int32).at[
        jnp.where(new_seg, sorted_gid, capacity)].set(perm, mode="drop")
    has_valid = jax.ops.segment_sum(validity.astype(jnp.int32), gid,
                                    num_segments=capacity) > 0
    out_data = jnp.take(data, winner, axis=0)
    out_lens = jnp.take(lengths, winner, axis=0)
    out_data = jnp.where(has_valid[:, None], out_data, 0)
    out_lens = jnp.where(has_valid, out_lens, 0)
    return out_data, has_valid, out_lens


def _identity_for(dtype, kind: str):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if kind == "min" else -jnp.inf, dtype)
    if dtype == jnp.bool_:
        return jnp.asarray(kind == "min", dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if kind == "min" else info.min, dtype)
