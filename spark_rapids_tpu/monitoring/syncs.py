"""Host-sync attribution on the span stream (scripts/syncprof.py's
engine, promoted into the monitoring subsystem).

A device->host read makes the driver wait for the device (0.9 ms for a
dispatch-and-read on the attached v5e, PR 21) and drains the dispatch
queue, so query wall time ~= device compute + syncs * that floor. This
wraps every sync
funnel (``jax.device_get``, ``ArrayImpl.__array__`` / ``__int__`` /
``__float__`` / ``__bool__`` / ``__index__``) and records each blocking
read as a ``sync`` span (LEVEL_KERNEL) whose args carry the innermost
engine call sites — the "where do the round trips come from" view a
device trace alone does not give. The spans interleave
with the operator/upload/shuffle spans on the same timeline, so a
Perfetto export shows each round trip *inside* the operator that paid
for it.

Install once per process (:func:`install`); the wrappers stay resident
but record nothing while the recorder is disabled or below
LEVEL_KERNEL, so installation is safe outside profiling runs too.
"""

from __future__ import annotations

import traceback
from typing import Dict, List, Tuple

from spark_rapids_tpu.monitoring import recorder

_INSTALLED = False


def _site() -> str:
    """Innermost TWO spark_rapids_tpu frames (helper + its caller)."""
    frames = []
    for f in reversed(traceback.extract_stack()):
        if "spark_rapids_tpu" in f.filename and \
                "/monitoring/" not in f.filename:
            short = f.filename.split("spark_rapids_tpu/")[-1]
            frames.append(f"{short}:{f.lineno} {f.name}")
            if len(frames) == 2:
                break
    return " <- ".join(frames) if frames else "<outside engine>"


def _wrap(fn, label: str):
    def wrapper(*a, **k):
        if not recorder.enabled() or \
                recorder.level() < recorder.LEVEL_KERNEL:
            return fn(*a, **k)
        with recorder.span(label, "sync", level=recorder.LEVEL_KERNEL,
                           args={"site": _site()}):
            return fn(*a, **k)
    wrapper.__wrapped__ = fn
    return wrapper


def install() -> None:
    """Wrap the jax sync funnels (idempotent)."""
    global _INSTALLED
    if _INSTALLED:
        return
    import jax
    from jax._src import array as _arr
    jax.device_get = _wrap(jax.device_get, "device_get")
    for m in ("__array__", "__int__", "__float__", "__bool__",
              "__index__"):
        if hasattr(_arr.ArrayImpl, m):
            setattr(_arr.ArrayImpl, m,
                    _wrap(getattr(_arr.ArrayImpl, m), m))
    _INSTALLED = True


def sync_stats(query_id=None) -> Dict[str, Tuple[int, float]]:
    """Aggregate recorded sync spans: ``label @ site`` -> (count, secs)
    — the exact shape scripts/syncprof.py reports."""
    stats: Dict[str, List[float]] = {}
    for e in recorder.events(query_id):
        ph, name, cat, ts, dur, tid, qid, args = e
        if ph != "X" or cat != "sync":
            continue
        a = args or {}
        # timed(m, "sizesPullTime") spans are syncs too — their "site"
        # is the metric name on the owning operator.
        site = a.get("site") or a.get("metric") or "<unknown>"
        s = stats.setdefault(f"{name} @ {site}", [0, 0.0])
        s[0] += 1
        s[1] += dur / 1e9
    return {k: (int(v[0]), v[1]) for k, v in stats.items()}
