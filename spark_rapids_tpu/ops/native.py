"""Native Pallas kernel layer for the hot device loops.

The reference accelerator routes *every* kernel through hand-tuned native
libcudf code reached over JNI (PAPER.md L0); until this module, our device
compute was pure jax.numpy composition lowered by XLA. The flight recorder
(PR 9) put numbers on where device time goes, and the top sinks are exactly
the loops XLA lowers worst on TPU:

- ``_radix_perm``'s per-digit LSD passes (ops/kernels.py) — every stable
  ``jnp.argsort`` is an O(n log^2 n) bitonic sort network on TPU. The
  native kernel is a *linear* stable counting-sort rank per 8-bit digit:
  per-block histograms, scanned digit/block bases, and a stable
  within-block prefix, all dense VPU work.
- the hash-join probe (ops/join.py ``probe_ranges``) — two separate
  ``jnp.searchsorted`` dispatches over the sorted build fingerprints
  become ONE branchless lower+upper binary search over two u32 planes.
- wire v2's RLE decode (columnar/wire.py) — ``searchsorted`` over the run
  ends plus a gather becomes one interval-membership select over the run
  table (bit patterns only, so -0.0/NaN payloads survive exactly).
- the sorted-segment groupby reduction (ops/kernels.py
  ``segment_reduce``) — scatter-based ``jax.ops.segment_*`` becomes a
  single-sweep segmented scan: Hillis-Steele within a block, a
  sequential-grid carry across blocks (TPU grid steps run in order on a
  core, which Pallas guarantees and the interpreter emulates).

Contracts (mirroring every other gate in this engine):

- **Bit identity.** Each kernel's output is bit-identical to its
  jax.numpy twin; tests/test_native.py pins the whole dtype ladder
  including -0.0/NaN float edge cases. Where bit identity cannot be
  guaranteed (float SUM reduction order, the unstable-first sort
  relaxation), the native path simply does not engage.
- **Default off until it compiles for the chip.** A kernel's
  ``native.<kernel>.enabled`` gate defaults on only when
  tests/test_chip_compile.py compiles it for v5e at real width and it has
  been seen bit-identical to its twin on a chip. Today none does: Mosaic
  refuses all four as written (ROADMAP A5 records the compiler's words),
  so all four gates default OFF on every backend. Turning one on by conf
  on a TPU backend raises the lowering error at trace time — there is no
  catch-and-fall-through to the twin.
- **Kill switches.** ``spark.rapids.sql.native.enabled`` is the master
  gate; ``SRT_NATIVE=0`` disables for a whole process. Off restores the
  jax.numpy code paths byte-for-byte.
- **Backend.** Mosaic only compiles on TPU. On any other backend the
  layer no-ops to the twin; the Pallas interpreter is reachable ONLY
  through the test hook :func:`forced` (the CPU parity suite).
- **Cache coherence.** :func:`fingerprint` folds the enabled-kernel set
  into every kernel-cache key (ops/kernel_cache.py ``lookup``) and the
  wire decode-jit cache, so toggling a gate never serves a stale
  compiled program.

Config is adopted process-globally per collect (``maybe_configure``),
like the wire codec — these kernels run deep inside traced code with no
conf in scope.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import config as C

KERNELS = ("radixSort", "joinProbe", "rleDecode", "segmentReduce")
_ENTRIES = {"master": C.NATIVE_ENABLED, "radixSort": C.NATIVE_RADIX_SORT,
            "joinProbe": C.NATIVE_JOIN_PROBE,
            "rleDecode": C.NATIVE_RLE_DECODE,
            "segmentReduce": C.NATIVE_SEGMENT_REDUCE}

_LOCK = threading.Lock()
# Conf-adopted overrides: None = fall through to env/default.
_OVERRIDE: Dict[str, Optional[bool]] = {"master": None}
_OVERRIDE.update({k: None for k in KERNELS})
_MAX_RUNS_OVERRIDE: Optional[int] = None
_FORCED: Optional[Dict[str, bool]] = None     # tests: forced() context
# Trace-time dispatch counters (a kernel inside a jitted program traces
# once and executes many times; these count traces, which is what the
# bench `native` block and the gating tests need).
_COUNTERS: Dict[str, float] = {}


def _interpret() -> bool:
    """Pallas interpreter: only inside :func:`forced` on a non-TPU
    backend. No env var, conf key or backend check turns it on."""
    return _FORCED is not None and jax.default_backend() != "tpu"


def available() -> bool:
    """Native kernels can run at all: a real TPU backend compiles them
    through Mosaic; anything else only under :func:`forced`."""
    return jax.default_backend() == "tpu" or _FORCED is not None


def maybe_configure(conf) -> None:
    """Adopt explicitly-set ``spark.rapids.sql.native.*`` keys for the
    process (unset keys clear back to env/default), mirroring the wire
    codec's process-global adoption."""
    global _MAX_RUNS_OVERRIDE
    with _LOCK:
        for name, entry in _ENTRIES.items():
            raw = conf.raw.get(entry.key)
            _OVERRIDE[name] = None if raw is None else bool(entry.get(conf))
        raw = conf.raw.get(C.NATIVE_RLE_MAX_RUNS.key)
        _MAX_RUNS_OVERRIDE = None if raw is None \
            else int(conf.get(C.NATIVE_RLE_MAX_RUNS))


def master_enabled() -> bool:
    if _FORCED is not None:
        return bool(_FORCED.get("master", True))
    with _LOCK:
        ov = _OVERRIDE["master"]
    if ov is not None:
        return ov
    v = os.environ.get("SRT_NATIVE")
    if v is not None:
        return v.strip() not in ("0", "false", "no", "")
    return bool(_ENTRIES["master"].default)


def gate_enabled(name: str) -> bool:
    """One kernel's own gate: adopted conf key, else its registered
    default (off for every kernel Mosaic refuses today)."""
    assert name in KERNELS, name
    with _LOCK:
        ov = _OVERRIDE[name]
    return ov if ov is not None else bool(_ENTRIES[name].default)


def kernel_enabled(name: str) -> bool:
    """Is one native kernel live right now (master gate + per-kernel
    gate + backend availability)?"""
    assert name in KERNELS, name
    if _FORCED is not None:
        return bool(_FORCED.get("master", True)) and \
            bool(_FORCED.get(name, True))
    return master_enabled() and available() and gate_enabled(name)


def rle_max_runs() -> int:
    with _LOCK:
        if _MAX_RUNS_OVERRIDE is not None:
            return _MAX_RUNS_OVERRIDE
    return int(C.NATIVE_RLE_MAX_RUNS.default)


def fingerprint() -> Tuple:
    """Folded into every kernel-cache key: the set of live native
    kernels (+ interpret mode, which changes the lowering). Toggling a
    gate therefore never serves a compiled program traced under the
    other setting."""
    live = tuple(k for k in KERNELS if kernel_enabled(k))
    if not live:
        return ()
    return ("native", live, "interp" if _interpret() else "mosaic")


class forced:
    """Test hook: force the native gate state for a ``with`` scope — and,
    on a non-TPU backend, the Pallas interpreter (the only way to reach
    it).

    ``forced()`` turns every kernel on whatever its default;
    ``forced(radixSort=False)`` keeps the master gate on with one kernel
    off; ``forced(master=False)`` disables everything."""

    def __init__(self, **kw: bool):
        self._kw = dict(kw)
        self._prev_forced = None

    def __enter__(self):
        global _FORCED
        self._prev_forced = _FORCED
        _FORCED = self._kw
        return self

    def __exit__(self, *exc):
        global _FORCED
        _FORCED = self._prev_forced
        return False


def _count(name: str) -> None:
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + 1


def counters() -> Dict[str, float]:
    with _LOCK:
        out = dict(_COUNTERS)
    out["nativeEnabled"] = bool(master_enabled() and available())
    out["nativeKernels"] = [k for k in KERNELS if kernel_enabled(k)]
    return out


def reset_counters() -> None:
    with _LOCK:
        _COUNTERS.clear()


# ---------------------------------------------------------------------------
# Block geometry. Capacity buckets are 2^k or 3*2^(k-1) (columnar/batch.py),
# so a 512/384 block always divides the capacity exactly — no remainder
# masking inside the kernels.
# ---------------------------------------------------------------------------

def _block(cap: int, limit: int = 512) -> int:
    if cap <= limit:
        return cap
    if cap % limit == 0:
        return limit
    b = limit * 3 // 4                     # 384 divides every 3*2^(k-1) rung
    assert cap % b == 0, f"capacity {cap} not divisible by {limit}/{b}"
    return b


def _pallas(kernel, **kw):
    from jax.experimental import pallas as pl
    return pl.pallas_call(kernel, interpret=_interpret(), **kw)


# ---------------------------------------------------------------------------
# Kernel 1: stable u32 radix rank (the LSD sort passes)
# ---------------------------------------------------------------------------
#
# One stable argsort of a (cap,) uint32 array = 4 stable counting-sort
# passes over 8-bit digits. Per digit pass:
#   hist kernel : per-block 256-bucket histogram (one-hot sum, dense VPU)
#   (jnp glue)  : digit bases = exclusive scan of totals; block bases =
#                 digit base + exclusive scan of block histograms
#   rank kernel : rank[i] = base[block, digit] + stable within-block
#                 prefix (exclusive one-hot column cumsum)
#   (jnp glue)  : permutation scatter (linear)
#
# Stability is by construction (block-major, row order), and a stable sort
# permutation is unique — hence bit-identical to jnp.argsort(stable=True).

_RADIX_BUCKETS = 256


def _hist_kernel(dig_ref, hist_ref):
    d = dig_ref[:].reshape(-1, 1)
    buckets = jax.lax.broadcasted_iota(
        jnp.int32, (d.shape[0], _RADIX_BUCKETS), 1)
    hist_ref[0, :] = jnp.sum((d == buckets).astype(jnp.int32),
                             axis=0).astype(jnp.int32)


def _rank_kernel(dig_ref, base_ref, rank_ref):
    d = dig_ref[:].reshape(-1, 1)
    buckets = jax.lax.broadcasted_iota(
        jnp.int32, (d.shape[0], _RADIX_BUCKETS), 1)
    onehot = (d == buckets).astype(jnp.int32)
    # Exclusive within-block stable prefix per bucket.
    prefix = jnp.cumsum(onehot, axis=0).astype(jnp.int32) - onehot
    rank_ref[:] = jnp.sum(onehot * (base_ref[0, :][None, :] + prefix),
                          axis=1).astype(jnp.int32)


def _digit_rank(dig: jnp.ndarray, cap: int) -> jnp.ndarray:
    """Stable counting-sort rank of one 8-bit digit array."""
    from jax.experimental import pallas as pl
    blk = _block(cap)
    nblocks = cap // blk
    hist = _pallas(
        _hist_kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((blk,), lambda b: (b,))],
        out_specs=pl.BlockSpec((1, _RADIX_BUCKETS), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, _RADIX_BUCKETS),
                                       jnp.int32),
    )(dig)
    totals = jnp.sum(hist, axis=0).astype(jnp.int32)
    digit_base = jnp.cumsum(totals).astype(jnp.int32) - totals
    block_excl = jnp.cumsum(hist, axis=0).astype(jnp.int32) - hist
    block_base = (digit_base[None, :] + block_excl).astype(jnp.int32)
    return _pallas(
        _rank_kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((blk,), lambda b: (b,)),
                  pl.BlockSpec((1, _RADIX_BUCKETS), lambda b: (b, 0))],
        out_specs=pl.BlockSpec((blk,), lambda b: (b,)),
        out_shape=jax.ShapeDtypeStruct((cap,), jnp.int32),
    )(dig, block_base)


def stable_argsort_u32(keyed: jnp.ndarray) -> jnp.ndarray:
    """Native twin of ``jnp.argsort(keyed, stable=True)`` for (cap,)
    uint32 keys: 4 LSD counting-sort digit passes."""
    _count("nativeRadixSortTraces")
    cap = keyed.shape[0]
    cur = jnp.arange(cap, dtype=jnp.int32)
    for shift in (0, 8, 16, 24):
        k = jnp.take(keyed, cur, axis=0)
        dig = ((k >> jnp.uint32(shift)) & jnp.uint32(0xFF)).astype(jnp.int32)
        rank = _digit_rank(dig, cap)
        cur = jnp.zeros((cap,), jnp.int32).at[rank].set(cur)
    return cur


# ---------------------------------------------------------------------------
# Kernel 2: fused hash-join probe (lower+upper bound over u64 fingerprints)
# ---------------------------------------------------------------------------

def _probe_kernel_factory(cap_b: int):
    # Descending power-of-two steps covering any capacity rung.
    steps = []
    s = 1
    while s * 2 <= cap_b:
        s *= 2
    while s >= 1:
        steps.append(s)
        s //= 2

    def kernel(bh_ref, bl_ref, qh_ref, ql_ref, lo_ref, hi_ref):
        bh = bh_ref[:]
        bl = bl_ref[:]
        qh = qh_ref[:]
        ql = ql_ref[:]
        lo = jnp.zeros(qh.shape, jnp.int32)
        hi = jnp.zeros(qh.shape, jnp.int32)
        n = jnp.int32(cap_b)
        for s in steps:
            for is_hi in (False, True):
                pos = hi if is_hi else lo
                nxt = pos + jnp.int32(s)
                idx = nxt - 1
                ah = jnp.take(bh, idx, axis=0)
                al = jnp.take(bl, idx, axis=0)
                if is_hi:       # count of build <= key (searchsorted right)
                    cmp = (ah < qh) | ((ah == qh) & (al <= ql))
                else:           # count of build <  key (searchsorted left)
                    cmp = (ah < qh) | ((ah == qh) & (al < ql))
                ok = (nxt <= n) & cmp
                if is_hi:
                    hi = jnp.where(ok, nxt, hi)
                else:
                    lo = jnp.where(ok, nxt, lo)
        lo_ref[:] = lo
        hi_ref[:] = hi
    return kernel


def searchsorted_u64_pair(built_fp: jnp.ndarray, probe_fp: jnp.ndarray):
    """Native twin of the probe's two ``jnp.searchsorted`` calls:
    ``(left, right)`` insertion points of every probe fingerprint in the
    sorted build fingerprints, as int32."""
    from jax.experimental import pallas as pl
    _count("nativeJoinProbeTraces")
    cap_b = built_fp.shape[0]
    cap_p = probe_fp.shape[0]
    bh = (built_fp >> jnp.uint64(32)).astype(jnp.uint32)
    bl = (built_fp & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    qh = (probe_fp >> jnp.uint64(32)).astype(jnp.uint32)
    ql = (probe_fp & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    blk = _block(cap_p)
    lo, hi = _pallas(
        _probe_kernel_factory(cap_b),
        grid=(cap_p // blk,),
        in_specs=[pl.BlockSpec((cap_b,), lambda b: (0,)),
                  pl.BlockSpec((cap_b,), lambda b: (0,)),
                  pl.BlockSpec((blk,), lambda b: (b,)),
                  pl.BlockSpec((blk,), lambda b: (b,))],
        out_specs=(pl.BlockSpec((blk,), lambda b: (b,)),
                   pl.BlockSpec((blk,), lambda b: (b,))),
        out_shape=(jax.ShapeDtypeStruct((cap_p,), jnp.int32),
                   jax.ShapeDtypeStruct((cap_p,), jnp.int32)),
    )(bh, bl, qh, ql)
    return lo, hi


# ---------------------------------------------------------------------------
# Kernel 3: wire v2 RLE decode (interval-membership select)
# ---------------------------------------------------------------------------

def _rle_kernel_factory(blk: int, run_cap: int, planes: int):
    def kernel(prev_ref, ends_ref, vals_ref, nrows_ref, out_ref):
        from jax.experimental import pallas as pl
        r0 = pl.program_id(0) * blk
        rows = r0 + jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0)
        prev = prev_ref[:].reshape(1, run_cap)
        ends = ends_ref[:].reshape(1, run_cap)
        mask = (prev <= rows) & (rows < ends)          # (blk, run_cap)
        live = rows < nrows_ref[0]                     # (blk, 1)
        vals = vals_ref[:]                             # (run_cap, planes)
        for p in range(planes):
            sel = jnp.sum(jnp.where(mask, vals[:, p][None, :], 0),
                          axis=1).astype(jnp.int32)
            out_ref[:, p] = jnp.where(live[:, 0], sel, jnp.int32(0))
    return kernel


def rle_decode(run_vals: jnp.ndarray, run_ends: jnp.ndarray, cap: int,
               num_rows) -> jnp.ndarray:
    """Native twin of the RLE decode's searchsorted+gather chain: expand
    the run table to (cap,) values in the wire dtype, padding rows
    zeroed. Bit patterns move through int32 planes, so float payloads
    (-0.0, NaN) reconstruct exactly."""
    from jax.experimental import pallas as pl
    _count("nativeRleDecodeTraces")
    run_cap = run_vals.shape[0]
    dt_ = run_vals.dtype
    itemsize = np.dtype(dt_).itemsize
    if itemsize == 8:
        planes = jax.lax.bitcast_convert_type(
            run_vals.reshape(run_cap, 1), jnp.int32).reshape(run_cap, 2)
    elif itemsize == 4:
        planes = jax.lax.bitcast_convert_type(
            run_vals, jnp.int32).reshape(run_cap, 1)
    else:                       # int8/int16 sign-extend (exact round trip)
        planes = run_vals.astype(jnp.int32).reshape(run_cap, 1)
    npl = planes.shape[1]
    prev = jnp.concatenate([jnp.zeros((1,), run_ends.dtype), run_ends[:-1]])
    blk = _block(cap)
    nrows = jnp.asarray(num_rows, jnp.int32).reshape(1)
    out = _pallas(
        _rle_kernel_factory(blk, run_cap, npl),
        grid=(cap // blk,),
        in_specs=[pl.BlockSpec((run_cap,), lambda b: (0,)),
                  pl.BlockSpec((run_cap,), lambda b: (0,)),
                  pl.BlockSpec((run_cap, npl), lambda b: (0, 0)),
                  pl.BlockSpec((1,), lambda b: (0,))],
        out_specs=pl.BlockSpec((blk, npl), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((cap, npl), jnp.int32),
    )(prev.astype(jnp.int32), run_ends.astype(jnp.int32), planes, nrows)
    if itemsize == 8:
        return jax.lax.bitcast_convert_type(out, dt_).reshape(cap)
    if itemsize == 4:
        return jax.lax.bitcast_convert_type(out[:, 0], dt_)
    return out[:, 0].astype(dt_)        # wrap-narrow, exact for the widen


# ---------------------------------------------------------------------------
# Kernel 4: sorted-segment reduction (segmented scan + boundary pick)
# ---------------------------------------------------------------------------
#
# ``segment_reduce``'s gid is group-sorted (nondecreasing), so the
# scatter-based jax.ops.segment_* is overkill: one segmented scan sweep
# produces per-row running reductions; the value at each segment's last
# row IS the segment result (scattered to its slot with unique indices).
#
# Everything runs in an exact encoded domain of 1-2 uint32 planes:
#   - integer sums: two's-complement add (wrap-exact, associative),
#     int64 as (hi, lo) with explicit carry;
#   - min/max: the total-order bit transform (floats: sign-flip trick,
#     so -0.0 < 0.0 exactly like XLA's minimum; ints: sign-bias flip),
#     identities chosen to decode to the twin's identities.
# Float SUMS never come here: reduction order changes rounding, and bit
# identity is the contract.

def _shift_down(x, d, fill):
    pad = jnp.full((d,) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([pad, x[:-d]], axis=0)


def _combine(kind: str, a_planes, b_planes):
    """combine(a, b) where a precedes b; returns planes of the result."""
    if kind == "sum32":
        return (a_planes[0] + b_planes[0],)
    if kind == "sum64":
        ah, al = a_planes
        bh, bl = b_planes
        lo = al + bl
        carry = (lo < al).astype(jnp.uint32)
        return (ah + bh + carry, lo)
    # min/max over 1 or 2 unsigned planes, lexicographic.
    if len(a_planes) == 1:
        a, b = a_planes[0], b_planes[0]
        pick_a = a < b if kind == "min" else a > b
        return (jnp.where(pick_a, a, b),)
    ah, al = a_planes
    bh, bl = b_planes
    a_lt = (ah < bh) | ((ah == bh) & (al < bl))
    pick_a = a_lt if kind == "min" else \
        (ah > bh) | ((ah == bh) & (al > bl))
    return (jnp.where(pick_a, ah, bh), jnp.where(pick_a, al, bl))


def _segscan_kernel_factory(blk: int, planes: int, kind: str,
                            identity: Tuple[int, ...]):
    steps = []
    d = 1
    while d < blk:
        steps.append(d)
        d *= 2

    def kernel(flag_ref, pl_refs, out_ref, carry_ref):
        from jax.experimental import pallas as pl
        b = pl.program_id(0)

        @pl.when(b == 0)
        def _():
            for p in range(planes):
                carry_ref[0, p] = jnp.uint32(identity[p])

        # Hillis-Steele over the segmented-scan monoid (g, v):
        #   (g1,v1) + (g2,v2) = (g1|g2, g2 ? v2 : combine(v1,v2))
        # with (g=0, v=identity) as the neutral fill beyond block start.
        g = flag_ref[:]                              # (blk,) int32 0/1
        v = tuple(pl_refs[:, p] for p in range(planes))
        for d in steps:
            g_sh = _shift_down(g, d, jnp.int32(0))
            v_sh = tuple(_shift_down(v[p], d, jnp.uint32(identity[p]))
                         for p in range(planes))
            comb = _combine(kind, v_sh, v)
            keep = g == 1
            v = tuple(jnp.where(keep, v[p], comb[p])
                      for p in range(planes))
            g = g | g_sh
        # Rows with no segment start inside this block continue the
        # carried segment from the previous block.
        open_ = g == 0
        carry = tuple(jnp.broadcast_to(carry_ref[0, p], (blk,))
                      for p in range(planes))
        fixed = _combine(kind, carry, v)
        v = tuple(jnp.where(open_, fixed[p], v[p]) for p in range(planes))
        for p in range(planes):
            out_ref[:, p] = v[p]
            carry_ref[0, p] = v[p][blk - 1]
    return kernel


def _segscan(flags: jnp.ndarray, planes: jnp.ndarray, kind: str,
             identity: Tuple[int, ...]) -> jnp.ndarray:
    """Per-row running segmented reduction over (cap, P) uint32 planes.
    ``flags[i]`` = 1 iff row i starts a segment (row 0 included)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    cap, npl = planes.shape
    blk = _block(cap)
    return _pallas(
        _segscan_kernel_factory(blk, npl, kind, identity),
        grid=(cap // blk,),
        in_specs=[pl.BlockSpec((blk,), lambda b: (b,)),
                  pl.BlockSpec((blk, npl), lambda b: (b, 0))],
        out_specs=pl.BlockSpec((blk, npl), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((cap, npl), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((1, npl), jnp.uint32)],
    )(flags.astype(jnp.int32), planes)


def _bitcast(x, dt_):
    return jax.lax.bitcast_convert_type(x, dt_)


def _minmax_encode(values: jnp.ndarray):
    """Exact total-order encode to uint32 planes; returns (planes list,
    decode fn) or None when the dtype has no exact encode here."""
    dt_ = values.dtype
    if dt_ == jnp.bool_:
        enc = _bitcast(values.astype(jnp.int32), jnp.uint32) \
            ^ jnp.uint32(0x80000000)

        def dec(planes):
            return _bitcast(planes[0] ^ jnp.uint32(0x80000000),
                            jnp.int32) != 0
        return [enc], dec
    if jnp.issubdtype(dt_, jnp.integer) and np.dtype(dt_).itemsize <= 4:
        enc = _bitcast(values.astype(jnp.int32), jnp.uint32) \
            ^ jnp.uint32(0x80000000)

        def dec(planes):
            return _bitcast(planes[0] ^ jnp.uint32(0x80000000),
                            jnp.int32).astype(dt_)
        return [enc], dec
    if jnp.issubdtype(dt_, jnp.integer):          # int64 / timestamp
        u = _bitcast(values, jnp.uint64) ^ jnp.uint64(0x8000000000000000)
        hi = (u >> jnp.uint64(32)).astype(jnp.uint32)
        lo = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)

        def dec(planes):
            u_ = (planes[0].astype(jnp.uint64) << jnp.uint64(32)) | \
                planes[1].astype(jnp.uint64)
            return _bitcast(u_ ^ jnp.uint64(0x8000000000000000), dt_)
        return [hi, lo], dec
    if dt_ == jnp.float32:
        bits = _bitcast(values, jnp.uint32)
        neg = (bits >> jnp.uint32(31)) == 1
        enc = jnp.where(neg, ~bits, bits | jnp.uint32(0x80000000))

        def dec(planes):
            e = planes[0]
            pos = (e & jnp.uint32(0x80000000)) != 0
            bits_ = jnp.where(pos, e ^ jnp.uint32(0x80000000), ~e)
            return _bitcast(bits_, jnp.float32)
        return [enc], dec
    if dt_ == jnp.float64:
        if jax.default_backend() == "tpu":
            return None         # emulated f64 cannot bitcast on TPU
        bits = _bitcast(values, jnp.uint64)
        neg = (bits >> jnp.uint64(63)) == 1
        enc = jnp.where(neg, ~bits, bits | jnp.uint64(0x8000000000000000))
        hi = (enc >> jnp.uint64(32)).astype(jnp.uint32)
        lo = (enc & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)

        def dec(planes):
            e = (planes[0].astype(jnp.uint64) << jnp.uint64(32)) | \
                planes[1].astype(jnp.uint64)
            pos = (e & jnp.uint64(0x8000000000000000)) != 0
            bits_ = jnp.where(pos, e ^ jnp.uint64(0x8000000000000000), ~e)
            return _bitcast(bits_, jnp.float64)
        return [hi, lo], dec
    return None


def _encoded_identity(np_dtype, kind: str) -> Tuple[int, ...]:
    """Encoded identity planes computed in NUMPY (this runs at trace
    time). The identity must DECODE to exactly the twin's
    ``jax.ops.segment_min``/``segment_max`` empty-segment fill (dtype
    max/min, +/-inf for floats), and no encoded value may beat it in
    the total order — true by construction since it encodes the
    dtype's extreme (the twin masks NaN before reducing, so the float
    extremes are the infinities)."""
    if np.issubdtype(np_dtype, np.floating):
        ext = np.asarray(np.inf if kind == "min" else -np.inf, np_dtype)
        if np_dtype == np.dtype(np.float32):
            bits = int(ext.view(np.uint32))
            enc = (~bits & 0xFFFFFFFF) if bits >> 31 else bits | 0x80000000
            return (enc,)
        bits = int(ext.view(np.uint64))
        enc = (~bits & (2 ** 64 - 1)) if bits >> 63 else \
            bits | 0x8000000000000000
        return (enc >> 32, enc & 0xFFFFFFFF)
    if np_dtype == np.dtype(np.bool_):
        v = 1 if kind == "min" else 0
        return ((v ^ 0x80000000),)
    info = np.iinfo(np_dtype)
    v = info.max if kind == "min" else info.min
    if np_dtype.itemsize <= 4:
        return (((v & 0xFFFFFFFF) ^ 0x80000000),)
    u = (v & (2 ** 64 - 1)) ^ (1 << 63)
    return (u >> 32, u & 0xFFFFFFFF)


def _segment_finish(running: jnp.ndarray, gid: jnp.ndarray, capacity: int,
                    identity: Tuple[int, ...]) -> jnp.ndarray:
    """Scatter each segment's last running value to its slot; empty
    slots keep the (encoded) identity. Indices are unique (gid is
    nondecreasing), so .set is race-free."""
    cap = gid.shape[0]
    is_last = jnp.concatenate([gid[1:] != gid[:-1],
                               jnp.ones((1,), jnp.bool_)])
    slots = jnp.where(is_last, gid, capacity)
    npl = running.shape[1]
    init = jnp.tile(jnp.asarray(identity, jnp.uint32)[None, :],
                    (capacity, 1))
    return init.at[slots].set(running, mode="drop")


def _flags_of(gid: jnp.ndarray) -> jnp.ndarray:
    return jnp.concatenate([jnp.ones((1,), jnp.bool_),
                            gid[1:] != gid[:-1]]).astype(jnp.int32)


def segment_sum_sorted(values: jnp.ndarray, gid: jnp.ndarray,
                       capacity: int) -> Optional[jnp.ndarray]:
    """Native twin of ``jax.ops.segment_sum`` for nondecreasing ids.
    Returns None when the dtype is not exactly summable here (floats:
    reduction order changes rounding)."""
    dt_ = values.dtype
    if jnp.issubdtype(dt_, jnp.floating) or dt_ == jnp.bool_:
        return None
    _count("nativeSegmentReduceTraces")
    flags = _flags_of(gid)
    if np.dtype(dt_).itemsize <= 4:
        planes = jnp.stack(
            [_bitcast(values.astype(jnp.int32), jnp.uint32)], axis=1)
        running = _segscan(flags, planes, "sum32", (0,))
        out = _segment_finish(running, gid, capacity, (0,))
        return _bitcast(out[:, 0], jnp.int32).astype(dt_)
    u = _bitcast(values, jnp.uint64)
    planes = jnp.stack([(u >> jnp.uint64(32)).astype(jnp.uint32),
                        (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)],
                       axis=1)
    running = _segscan(flags, planes, "sum64", (0, 0))
    out = _segment_finish(running, gid, capacity, (0, 0))
    u_ = (out[:, 0].astype(jnp.uint64) << jnp.uint64(32)) | \
        out[:, 1].astype(jnp.uint64)
    return _bitcast(u_, dt_)


def segment_minmax_sorted(values: jnp.ndarray, gid: jnp.ndarray,
                          capacity: int, kind: str
                          ) -> Optional[jnp.ndarray]:
    """Native twin of ``jax.ops.segment_min``/``segment_max`` for
    nondecreasing ids, in the total-order bit domain. Returns None when
    the dtype has no exact encode (f64 on a real TPU)."""
    assert kind in ("min", "max")
    enc = _minmax_encode(values)
    if enc is None:
        return None
    _count("nativeSegmentReduceTraces")
    planes_list, dec = enc
    identity = _encoded_identity(np.dtype(values.dtype), kind)
    flags = _flags_of(gid)
    planes = jnp.stack(planes_list, axis=1)
    running = _segscan(flags, planes, kind, identity)
    out = _segment_finish(running, gid, capacity, identity)
    return dec([out[:, p] for p in range(out.shape[1])])
