"""Process-global compiled-kernel cache (the WholeStageCodegen serving
story's other half).

Every device operator used to hold its own ``self._jit = jax.jit(...)``
closure: a fresh query — every bench iteration, every partition-worth of a
TPC-H suite run, every new ``Planner`` — built NEW closures and re-traced
kernels the previous instance had already compiled (jax keys its program
cache on the closure object, not the computation). This module replaces
those scattered per-instance closures with one process-global LRU keyed by
*structural* identity: (expression-tree fingerprint, input schema,
capacity bucket). Two exec instances with equal fingerprints share one
jitted callable, so repeated execution pays compile cost exactly once per
process.

Design notes:
- Keys are plain hashable tuples built by :func:`fingerprint`, a generic
  structural walk (type names + scalar attrs + recursion into nested
  objects/arrays). Floats go through ``repr`` so NaN keys stay equal to
  themselves; callables hash by qualname + bytecode; arrays by content
  digest (range-partition bounds are data — equal bounds, equal kernel).
- Entries wrap the jitted callable in :class:`CompiledKernel`, which times
  the FIRST invocation (tracing + XLA compile happen there, synchronously)
  so operators can surface a ``compileTime`` metric.
- The cache is bounded by ``spark.rapids.sql.kernelCache.maxEntries``
  (LRU); hits/misses are counted globally and surfaced per-op through
  ``Metrics`` as ``kernelCacheHits`` / ``kernelCacheMisses``.

This module imports nothing from the ops/exprs/columnar layers (they all
import it), only stdlib + numpy.
"""

from __future__ import annotations

import collections
import hashlib
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

DEFAULT_MAX_ENTRIES = 1024


# ---------------------------------------------------------------------------
# Structural fingerprints
# ---------------------------------------------------------------------------

def fingerprint(obj: Any) -> Any:
    """Hashable structural fingerprint of ``obj``.

    Stable across instances and across processes-of-the-same-code for the
    object graphs that describe kernels: expression trees, sort orders,
    agg specs, window specs, partitionings (including sampled range
    bounds), schemas. Two objects with equal fingerprints must denote the
    same traced computation — the cache correctness contract."""
    return _fp(obj, 0)


_MAX_DEPTH = 32


def _fp(v: Any, depth: int) -> Any:
    if depth > _MAX_DEPTH:
        raise ValueError("fingerprint recursion too deep (cyclic kernel "
                         "descriptor?)")
    if v is None or isinstance(v, (bool, int, str, bytes)):
        return v
    if isinstance(v, float):
        # repr: NaN != NaN would make any NaN-bearing key unfindable.
        return ("f", repr(v))
    if isinstance(v, np.dtype):
        return ("npdt", v.str)
    if isinstance(v, np.generic):
        return ("npv", v.dtype.str, repr(v.item()))
    if type(v).__name__ == "BindSlotExpr":
        # Bound literals (exprs/bindslots.py) are VALUE-FREE by
        # construction: the key carries (slot, dtype) only, so two
        # bindings of the same dtype share ONE compiled kernel — the
        # binding arrives as a traced runtime input, never a trace
        # constant. Plain Literal nodes keep their value in the key
        # (the generic walk below), which stays correct: an unhoisted
        # literal IS a trace constant. Duck-typed on the class name so
        # this module keeps its no-engine-imports rule.
        return ("bindslot", v.slot, v.dtype.name)
    if isinstance(v, (list, tuple)):
        return tuple(_fp(x, depth + 1) for x in v)
    if isinstance(v, (set, frozenset)):
        return ("set",) + tuple(sorted(repr(_fp(x, depth + 1)) for x in v))
    if isinstance(v, dict):
        return ("dict",) + tuple(
            (_fp(k, depth + 1), _fp(x, depth + 1))
            for k, x in sorted(v.items(), key=lambda kv: repr(kv[0])))
    if isinstance(v, np.ndarray):
        if v.dtype == object:
            # Object arrays (host string columns): content, not pointers.
            return ("ndo", v.shape) + tuple(
                _fp(x, depth + 1) for x in v.ravel().tolist())
        return ("nd", v.dtype.str, v.shape,
                hashlib.sha1(np.ascontiguousarray(v).tobytes())
                .hexdigest())
    if hasattr(v, "__array__"):
        # Device arrays (range bounds that stayed on device, scalars).
        a = np.asarray(v)
        return _fp(a, depth + 1)
    if callable(v) and not hasattr(v, "__dict__"):
        code = getattr(v, "__code__", None)
        return ("fn", getattr(v, "__qualname__", type(v).__name__),
                hashlib.sha1(code.co_code).hexdigest() if code else "")
    # Generic object: type identity + instance attrs. Covers Expression
    # trees (children live in __dict__), SortOrder, AggSpec/AggFunction,
    # WindowExprSpec/WindowSpec/WindowFrame, Partitioning, HostBatch/
    # HostColumn (range bounds), DataType.
    d = getattr(v, "__dict__", None)
    if d is not None:
        code = getattr(v, "__code__", None)
        parts: List[Any] = [
            "obj", type(v).__module__, type(v).__qualname__]
        if code is not None:  # a function that also has attributes
            parts.append(hashlib.sha1(code.co_code).hexdigest())
        attrs = tuple((k, _fp(x, depth + 1))
                      for k, x in sorted(d.items())
                      if not k.startswith("_jit")
                      and not k.startswith("_phys"))
        return tuple(parts) + attrs
    # Opaque leaf with no state we can see: fall back to the type name
    # only if its repr carries no identity (addresses would poison keys).
    r = repr(v)
    if "0x" in r:
        r = type(v).__qualname__
    return ("opaque", type(v).__module__, type(v).__qualname__, r)


def schema_fingerprint(schema) -> Tuple:
    """Fingerprint of an exec output schema ((name, DataType), ...)."""
    return tuple((n, t.name) for n, t in schema)


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

class CompiledKernel:
    """A cached callable that records its first-call wall time.

    jax traces + compiles synchronously inside the first invocation of a
    jitted function, so ``compile_ns`` after the first call is a
    compile-inclusive measure — exactly the number ops report as their
    ``compileTime`` metric."""

    __slots__ = ("fn", "compile_ns", "compiled", "_lock")

    def __init__(self, fn: Callable):
        self.fn = fn
        self.compile_ns = 0
        self.compiled = False
        self._lock = threading.Lock()

    def __call__(self, *args, **kwargs):
        if not self.compiled:
            # Double-checked: two threads racing the first call (shared
            # kernel across concurrent pipelined queries) must record
            # compile time exactly once; the loser falls through to a
            # plain (already-compiled) dispatch.
            with self._lock:
                if not self.compiled:
                    t0 = time.perf_counter_ns()
                    out = self.fn(*args, **kwargs)
                    self.compile_ns = time.perf_counter_ns() - t0
                    self.compiled = True
                    return out
        return self.fn(*args, **kwargs)


class KernelCache:
    """Bounded LRU of compiled kernels keyed by structural fingerprints."""

    def __init__(self, max_entries: int = DEFAULT_MAX_ENTRIES):
        self._entries: "collections.OrderedDict[Any, Any]" = \
            collections.OrderedDict()
        # key -> query id that paid the compile (owner tag; None when
        # compiled outside a managed query). The cache itself stays
        # process-global — sharing compiled kernels across queries is
        # the point — but reservations are attributable.
        self._owners: Dict[Any, Any] = {}
        self._lock = threading.RLock()
        self.max_entries = max_entries
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def configure(self, max_entries: int):
        with self._lock:
            self.max_entries = max(int(max_entries), 1)
            self._evict()

    def get(self, key: Any, builder: Callable[[], Any]) -> Tuple[Any, bool]:
        """Return (value, hit). ``builder`` runs on miss; its result is
        stored verbatim (usually a :class:`CompiledKernel` or a tuple of
        them)."""
        with self._lock:
            try:
                entry = self._entries[key]
            except KeyError:
                pass
            except TypeError:
                raise TypeError(f"unhashable kernel-cache key: {key!r}")
            else:
                self._entries.move_to_end(key)
                self.hits += 1
                return entry, True
            self.misses += 1
            entry = builder()
            self._entries[key] = entry
            from spark_rapids_tpu import faults
            self._owners[key] = faults.current_query_id()
            self._evict()
            return entry, False

    def _evict(self):
        while len(self._entries) > self.max_entries:
            key, _ = self._entries.popitem(last=False)
            self._owners.pop(key, None)
            self.evictions += 1

    def owners(self) -> Dict[Any, Any]:
        """key -> owning query id (None = unmanaged compile)."""
        with self._lock:
            return dict(self._owners)

    def evict_owned(self, owner_ids, keep: int) -> int:
        """Per-tenant compile-budget enforcement (parallel/qos/): drop
        the OLDEST entries whose owner tag is in ``owner_ids`` until at
        most ``keep`` remain; returns how many were evicted. Evicted
        kernels recompile transparently on next use — a quota, not a
        correctness event."""
        owner_ids = set(owner_ids)
        with self._lock:
            owned = [k for k in self._entries
                     if self._owners.get(k) in owner_ids]
            drop = len(owned) - max(int(keep), 0)
            n = 0
            for k in owned:
                if n >= drop:
                    break
                self._entries.pop(k, None)
                self._owners.pop(k, None)
                self.evictions += 1
                n += 1
            return n

    def stats(self) -> Dict[str, int]:
        with self._lock:
            out = {"hits": self.hits, "misses": self.misses,
                   "evictions": self.evictions,
                   "entries": len(self._entries)}
        p = persistent_stats()
        if p["dir"]:
            out["persistentCacheDir"] = p["dir"]
            out["persistentCacheHits"] = p["hits"]
            out["persistentCacheMisses"] = p["misses"]
        return out

    def reset_stats(self):
        with self._lock:
            self.hits = self.misses = self.evictions = 0

    def clear(self):
        with self._lock:
            self._entries.clear()
            self._owners.clear()
            self.reset_stats()

    def keys(self) -> List[Any]:
        with self._lock:
            return list(self._entries.keys())


_CACHE = KernelCache()


def cache() -> KernelCache:
    """The process-global kernel cache."""
    return _CACHE


def lookup(kind: str, key_parts: Tuple, builder: Callable[[], Callable],
           metrics=None) -> CompiledKernel:
    """Fetch-or-build the kernel for ``(kind, *key_parts)``, wrapping the
    built callable in :class:`CompiledKernel`. When ``metrics`` is given,
    counts ``kernelCacheHits``/``kernelCacheMisses`` on it."""
    entry, hit = _CACHE.get((kind,) + tuple(key_parts),
                            lambda: CompiledKernel(builder()))
    if metrics is not None:
        metrics.add("kernelCacheHits" if hit else "kernelCacheMisses", 1)
    return entry


def call(entry: CompiledKernel, metrics, *args, **kwargs):
    """Invoke a cached kernel; if this call compiled it, surface the
    compile-inclusive first-call time as the op's ``compileTime``.

    Every cached-kernel dispatch is a pure batch->batch computation, so
    the whole funnel runs under the OOM escalation ladder
    (memory/oom.py) and carries the ``kernel`` fault-injection site —
    one hardened choke point instead of per-call-site wrappers."""
    from spark_rapids_tpu import faults
    from spark_rapids_tpu.memory.oom import retry_on_oom

    fresh = not entry.compiled

    def dispatch():
        faults.fault_point("kernel")
        return entry(*args, **kwargs)

    out = retry_on_oom(dispatch)
    if fresh and metrics is not None:
        metrics.add("compileTime", entry.compile_ns)
    return out


# ---------------------------------------------------------------------------
# Persistent (on-disk) compilation cache
# ---------------------------------------------------------------------------
#
# The in-memory LRU above survives re-planning but not process restarts:
# a fresh process pays first_run_s (trace + XLA compile) for every kernel
# again, and a chip call is always a fresh process. JAX's persistent
# compilation cache serializes compiled executables to disk so a restarted
# process deserializes (~ms) instead of recompiling (~s..min on a TPU).
#
# Exactly one rule picks the directory (package ``__init__`` applies it at
# import): ``JAX_COMPILATION_CACHE_DIR`` when set, else
# ``<checkout>/.jax_cache``. ``spark.rapids.sql.kernelCache.persistentDir``
# may move it only while the environment variable is unset; with the
# variable set the key just reports whether it names the active directory.
# Hits/misses are counted via jax's monitoring events and surface through
# :meth:`KernelCache.stats` as persistentCacheHits / persistentCacheMisses.

_PERSISTENT_LOCK = threading.Lock()
_PERSISTENT = {"hits": 0, "misses": 0, "listener": False}


def _on_cache_event(event: str, **kwargs) -> None:
    if event.endswith("/cache_hits"):
        with _PERSISTENT_LOCK:
            _PERSISTENT["hits"] += 1
    elif event.endswith("/cache_misses"):
        with _PERSISTENT_LOCK:
            _PERSISTENT["misses"] += 1


def _ensure_cache_listener() -> None:
    with _PERSISTENT_LOCK:
        if not _PERSISTENT["listener"]:
            import jax
            jax.monitoring.register_event_listener(_on_cache_event)
            _PERSISTENT["listener"] = True


def configure_persistent(path: Optional[str]) -> bool:
    """Adopt ``spark.rapids.sql.kernelCache.persistentDir`` (idempotent).
    Empty/None changes nothing: the directory stays where package import
    put it. With ``JAX_COMPILATION_CACHE_DIR`` set the environment owns
    the directory and this never moves it. Returns True when the cache is
    active at ``path``."""
    import jax
    _ensure_cache_listener()
    path = (path or "").strip()
    if not path:
        return False
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
            jax.config.jax_compilation_cache_dir == path:
        return jax.config.jax_compilation_cache_dir == path
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_compilation_cache_dir", path)
    # The engine's kernels compile in ms on warm backends; without
    # these floors jax would skip persisting exactly the cheap
    # kernels whose aggregate retrace cost dominates first_run_s.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax latches "is the cache usable" (and where) on the FIRST compile
    # of the process; reset it so the new dir takes effect mid-process.
    compilation_cache.reset_cache()
    return True


def persistent_stats() -> Dict[str, Any]:
    """The ACTIVE persistent-cache directory (None when off) and the
    hits/misses seen since the listener was registered (first plan)."""
    import jax
    with _PERSISTENT_LOCK:
        return {"dir": jax.config.jax_compilation_cache_dir or None,
                "hits": _PERSISTENT["hits"],
                "misses": _PERSISTENT["misses"]}


def detached_clone(op):
    """Shallow clone of an exec with its child links severed — jitting a
    BOUND METHOD for the global cache must not pin the exec's whole
    subtree (and through it the source data) in memory for the cache
    entry's lifetime. The kernels only read the op's own spec attributes
    (exprs/aggs/mode/...), never its children."""
    import copy
    clone = copy.copy(op)
    clone.children = ()
    return clone
