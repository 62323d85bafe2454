"""Host-sync attribution on the span stream.

A device->host read makes the driver wait for the device (0.9 ms for a
dispatch-and-read on the attached v5e, PR 21) and drains the dispatch
queue, so query wall time ~= device compute + syncs * that floor. This
wraps every sync
funnel (``jax.device_get``, ``ArrayImpl.__array__`` / ``__int__`` /
``__float__`` / ``__bool__`` / ``__index__``) and records each blocking
read as a ``sync`` span (LEVEL_KERNEL) — the "where do the round trips
come from" view a device trace alone does not give. The span's parent
chain names the operator (or ``download``, ``upload``...) that paid for
the round trip; its args carry the innermost engine call sites too, for
a sync outside any span. The spans interleave with the
operator/upload/shuffle spans on the same timeline, so a Perfetto export
shows each round trip *inside* the operator that paid for it.

Install once per process (:func:`install`); the wrappers stay resident
but record nothing while the recorder is disabled or below
LEVEL_KERNEL, so installation is safe outside profiling runs too.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Tuple

from spark_rapids_tpu.monitoring import recorder

_INSTALLED = False


def _site() -> str:
    """Innermost TWO spark_rapids_tpu frames (helper + its caller). A
    walk over the live frames: no source line is read (55 to 78 blocking
    reads a TPC-H query, PR 24)."""
    frames = []
    f = sys._getframe(2)        # past _site and the wrapper
    while f is not None:
        filename = f.f_code.co_filename
        if "spark_rapids_tpu" in filename and \
                "/monitoring/" not in filename:
            short = filename.split("spark_rapids_tpu/")[-1]
            frames.append(f"{short}:{f.f_lineno} {f.f_code.co_name}")
            if len(frames) == 2:
                break
        f = f.f_back
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


def _funnel(event: tuple) -> bool:
    return event[2] == "sync" and "site" in (event[7] or {})


def owner(event: tuple, by_sid: Dict[int, tuple]) -> str:
    """Who paid for a sync span: the nearest span above it that is no
    funnel span — ``<Op>:<metric>`` for an operator's ``timed()``
    section, ``<Op>:<name>`` for a span that names its operator
    (``HashJoinExec:shrink-all``), else the span's name (``download``,
    ``upload``...); the call site where no such span is open. A
    ``sizesPullTime`` section is its own owner."""
    up = event
    while up is not None and _funnel(up):
        up = by_sid.get(up[9])
    if up is None:
        return event[7].get("site") or "<unknown>"
    args = up[7] or {}
    if "metric" in args:
        return f"{up[1]}:{args['metric']}"
    return f"{args['op']}:{up[1]}" if args.get("op") else up[1]


def sync_stats(query_id=None) -> Dict[str, Tuple[int, float]]:
    """Aggregate the recorded sync spans: ``label @ owner`` -> (count,
    secs). A ``sizesPullTime``
    section that holds funnel spans is their owner and no sync of its
    own; below kernel level, where no funnel records, it is the sync."""
    evs = [e for e in recorder.events(query_id) if e[0] == "X"]
    by_sid = {e[8]: e for e in evs}
    holds_syncs = {e[9] for e in evs if e[2] == "sync"}
    stats: Dict[str, List[float]] = {}
    for e in evs:
        if e[2] != "sync" or e[8] in holds_syncs:
            continue
        s = stats.setdefault(f"{e[1]} @ {owner(e, by_sid)}", [0, 0.0])
        s[0] += 1
        s[1] += e[4] / 1e9
    return {k: (int(v[0]), v[1]) for k, v in stats.items()}
