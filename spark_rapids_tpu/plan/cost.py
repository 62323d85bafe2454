"""Cost-based host/device placement (ROADMAP item 1 / ISSUE 7 tentpole).

A device plan pays a fixed price per host sync — dispatch a program, wait,
read a scalar back: 0.90 ms median on the locally attached v5e (PR 21) —
and a query issues tens of them, so an input of a few MB finishes sooner
in one host pass — the reference's own economics say device offload is
"worthwhile >= 30s" (docs/FAQ.md:82-84). This module gives the planner
the number it was missing: a per-subtree estimate of device time
(sync floor x sync count + bytes over the device pipeline) vs host time
(bytes over the host engine, one pass per operator), grounded in the same
parquet/ORC footer stats that feed autoBroadcastJoinThreshold
(plan/pruning.py estimate_bytes, cached footer parses in io/scan.py).

Placement is maximal-subtree: the walk is top-down, and the FIRST node
whose whole subtree estimates cheaper on the host flips that entire
subtree to the host engine (the existing ``execute_host`` path, promoted
from the OOM-fallback rung to a first-class placement). The conversion
layer then bridges engines exactly as it does for capability fallbacks,
so a host-placed subtree under a device parent uploads once at its root.

Estimates are heuristics with conf-overridable constants
(``spark.rapids.sql.cost.*``; the sync floor is the PR 21 chip
measurement); they only steer placement — results are engine-independent
either way.

Gates (all leave the legacy all-device plan untouched):
- ``spark.rapids.sql.cost.enabled`` false, or ``SRT_COST=0``;
- test mode (``spark.rapids.sql.test.enabled`` asserts device planning);
- an armed fault schedule (chaos targets device dispatch sites);
- a non-inprocess shuffle transport (mesh/hostfile runs measure those
  paths, not placement);
- no file scan in the plan (in-memory/range plans have no footer stats
  to ground the model — unit-test currency stays on the device path).
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Dict, List, Optional

from spark_rapids_tpu import config as C
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.logical import LogicalPlan

# Process-global counters (mirrors pipeline.counters()): how often
# placement ran and what it chose.
_COUNTERS: Dict[str, float] = {}
_COUNTERS_LOCK = threading.Lock()


def _record(name: str, amount: float = 1) -> None:
    with _COUNTERS_LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + amount


def counters() -> Dict[str, float]:
    with _COUNTERS_LOCK:
        return dict(_COUNTERS)


def reset_counters() -> None:
    with _COUNTERS_LOCK:
        _COUNTERS.clear()


# ---------------------------------------------------------------------------
# Self-calibration: the model's two machine constants — the per-dispatch
# sync floor and the device pipeline throughput — default to what one
# attached v5e showed (PR 21). With cost.calibration.enabled the
# flight recorder's observed numbers EWMA into process-global effective
# values (clamped to [1/4x, 4x] of the configured constants), so
# placement tracks the machine it actually runs on. An explicitly-set
# cost.* conf key always wins over the calibrated value.
# ---------------------------------------------------------------------------

_CAL_LOCK = threading.Lock()
_CAL: Dict[str, Optional[float]] = {
    "sync_floor_ms": None, "device_gbps": None, "samples": 0.0,
    "last_error_pct": None}


def calibration_enabled(conf: "C.TpuConf") -> bool:
    if conf.raw.get(C.COST_CALIBRATION.key) is not None:
        return bool(conf.get(C.COST_CALIBRATION))
    env = os.environ.get("SRT_COST_CALIBRATION")
    if env is not None:
        return env.strip() not in ("0", "false", "no")
    return bool(C.COST_CALIBRATION.default)


def _clamped(value: float, default: float) -> float:
    return min(max(value, default / 4.0), default * 4.0)


def _cpu_only_backend() -> bool:
    """True when the "device" engine itself runs on the host CPU
    (JAX_PLATFORMS=cpu: the test suite, local dev). A sync there is a
    function return (~10 us), not a trip over PCIe to a chip, so the
    floor is charged as zero: with the chip's figure the suite's
    kilobyte fixtures would all price cheaper on the host engine and no
    test would drive the device operators."""
    import jax
    return jax.default_backend() == "cpu"


def effective_sync_floor_ms(conf: "C.TpuConf") -> float:
    """The sync floor the estimator charges: an explicit conf key wins;
    else zero on a CPU-only backend (see :func:`_cpu_only_backend`);
    else the calibrated observation (clamped); else the default."""
    configured = float(conf.get(C.COST_SYNC_FLOOR_MS))
    if conf.raw.get(C.COST_SYNC_FLOOR_MS.key) is not None:
        return configured
    if _cpu_only_backend():
        return 0.0
    if not calibration_enabled(conf):
        return configured
    with _CAL_LOCK:
        cal = _CAL["sync_floor_ms"]
    return configured if cal is None else _clamped(cal, configured)


def effective_device_gbps(conf: "C.TpuConf") -> float:
    configured = float(conf.get(C.COST_DEVICE_GBPS))
    if conf.raw.get(C.COST_DEVICE_GBPS.key) is not None or \
            not calibration_enabled(conf):
        return configured
    with _CAL_LOCK:
        cal = _CAL["device_gbps"]
    return configured if cal is None else _clamped(cal, configured)


def observe(sync_floor_ms: Optional[float] = None,
            device_gbps: Optional[float] = None,
            error_pct: Optional[float] = None,
            alpha: float = 0.2) -> None:
    """Fold one query's observations into the calibration state.
    ``error_pct`` (the Cost@query estimateErrorPct) dampens the update:
    a query whose byte estimates were far off earns less trust."""
    weight = alpha
    if error_pct is not None:
        weight = alpha / (1.0 + max(error_pct, 0.0) / 100.0)
    with _CAL_LOCK:
        if error_pct is not None:
            _CAL["last_error_pct"] = float(error_pct)
        for key, obs in (("sync_floor_ms", sync_floor_ms),
                         ("device_gbps", device_gbps)):
            if obs is None or obs <= 0:
                continue
            cur = _CAL[key]
            _CAL[key] = float(obs) if cur is None \
                else (1.0 - weight) * cur + weight * float(obs)
        if sync_floor_ms is not None or device_gbps is not None:
            _CAL["samples"] += 1
    _record("costCalibrationUpdates")


def calibration_state() -> Dict[str, Optional[float]]:
    with _CAL_LOCK:
        return dict(_CAL)


def reset_calibration() -> None:
    with _CAL_LOCK:
        _CAL.update({"sync_floor_ms": None, "device_gbps": None,
                     "samples": 0.0, "last_error_pct": None})


def observe_query(ctx) -> None:
    """Feed one finished query's flight-recorder spans (and its
    Cost@query estimateErrorPct) back into the calibration state.
    Called from the collect tail; a no-op when tracing is off (no spans
    to learn from) or calibration is disabled."""
    if not calibration_enabled(ctx.conf):
        return
    from spark_rapids_tpu import monitoring
    if not monitoring.enabled():
        return
    qid = ctx.cache.get("trace_query")
    if qid is None:
        return
    evs = monitoring.events(qid)
    sync_ns: List[float] = []
    upload_bytes = 0.0
    upload_ns = 0.0
    for e in evs:
        if e[0] != "X":
            continue
        cat, dur = e[2], e[4]
        if cat == "sync":
            sync_ns.append(dur)
        elif cat == "upload":
            args = e[7] or {}
            b = args.get("bytes")
            if b:
                upload_bytes += float(b)
                upload_ns += float(dur)
    sync_floor = (sum(sync_ns) / len(sync_ns)) / 1e6 if sync_ns else None
    gbps = (upload_bytes / (upload_ns / 1e9)) / 1e9 \
        if upload_ns > 0 and upload_bytes > 0 else None
    err = None
    try:
        # Read-only: query_metrics_entry would CREATE an empty
        # Cost@query group and change the query's metric shape.
        cm = ctx.metrics.get("Cost@query")
        if cm is not None:
            err = cm.values.get("estimateErrorPct")
    except Exception:
        pass
    if sync_floor is None and gbps is None:
        return
    alpha = float(ctx.conf.get(C.COST_CALIBRATION_ALPHA))
    observe(sync_floor_ms=sync_floor, device_gbps=gbps, error_pct=err,
            alpha=alpha)


def cost_enabled(conf: "C.TpuConf") -> bool:
    """Conf key wins; else the SRT_COST env (CI matrix hook); else the
    registered default."""
    if conf.raw.get(C.COST_ENABLED.key) is not None:
        return bool(conf.get(C.COST_ENABLED))
    env = os.environ.get("SRT_COST")
    if env is not None:
        return env.strip() not in ("0", "false", "no")
    return bool(C.COST_ENABLED.default)


def _placement_gates(conf: "C.TpuConf", plan: LogicalPlan) -> Optional[str]:
    """Why placement must not run, or None when it may."""
    if not cost_enabled(conf):
        return "disabled"
    if conf.test_enabled:
        return "test mode asserts device planning"
    if conf.raw.get(C.TEST_FAULTS.key) is not None or \
            os.environ.get("SRT_FAULTS", "").strip():
        return "fault schedule armed (chaos targets device sites)"
    from spark_rapids_tpu.parallel import transport as T
    if T.transport_name(conf) != "inprocess":
        return "non-inprocess shuffle transport"
    if not _has_file_scan(plan):
        return "no footer-stats-backed scan in the plan"
    return None


def _has_file_scan(plan: LogicalPlan) -> bool:
    if isinstance(plan, L.FileScan):
        return True
    return any(_has_file_scan(c) for c in plan.children)


# ---------------------------------------------------------------------------
# Per-node estimates
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class NodeEstimate:
    """One logical node's subtree estimate (totals INCLUDE children)."""

    name: str
    bytes_out: Optional[int]      # estimated output bytes (None = unknown)
    subtree_bytes: Optional[int]  # max bytes flowing through any node
    device_ms: float              # subtree device estimate
    host_ms: float                # subtree host estimate
    syncs: int                    # subtree device sync count


# Device host-sync round trips charged per node kind: how many times the
# node's execution forces the driver to wait on the device (exchange
# sizes pull + serve, join build stats pull + expansion count, aggregate
# shrink, range-sort sample). Scans charge one for the upload dispatch.
def _node_syncs(plan: LogicalPlan, conf: "C.TpuConf") -> int:
    if isinstance(plan, (L.FileScan, L.InMemoryScan, L.LogicalRange)):
        return 1
    if isinstance(plan, L.LogicalAggregate):
        # partial -> exchange (sizes + serve) -> final shrink.
        return 3
    if isinstance(plan, L.LogicalJoin):
        return _join_syncs(plan, conf)
    if isinstance(plan, L.LogicalSort):
        return 3                  # range sample + exchange + serve
    if isinstance(plan, L.LogicalWindow):
        return 3                  # hash exchange + partition sort
    if isinstance(plan, L.LogicalLimit):
        return 2                  # single-partition exchange
    if isinstance(plan, L.LogicalRepartition):
        # The exchange's sizes pull, then every reduce partition served
        # downstream is its own round trip — the term that makes a
        # tiny-input repartition a guaranteed device loss.
        return 1 + max(int(plan.num_partitions), 1)
    if isinstance(plan, L.LogicalGenerate):
        return 1
    return 0


def _join_syncs(plan: "L.LogicalJoin", conf: "C.TpuConf") -> int:
    """Broadcast: build collect + expansion-count pull. Shuffle: two
    exchanges (sizes + serve each) + build + expansion."""
    strategy = plan.strategy
    if strategy == "auto" and plan.join_type != "full":
        from spark_rapids_tpu.plan.pruning import estimate_bytes
        threshold = int(conf.get(C.AUTO_BROADCAST_THRESHOLD))
        build = plan.children[1] if plan.join_type != "right" \
            else plan.children[0]
        est = estimate_bytes(build)
        strategy = "broadcast" if threshold >= 0 and est is not None \
            and est <= threshold else "shuffle"
    return 2 if strategy == "broadcast" else 6


def estimate_plan(plan: LogicalPlan, conf: "C.TpuConf",
                  out: Optional[Dict[int, NodeEstimate]] = None,
                  ) -> Dict[int, NodeEstimate]:
    """Bottom-up estimates for every node, keyed by id(plan)."""
    from spark_rapids_tpu.plan.pruning import estimate_bytes
    if out is None:
        out = {}
    for c in plan.children:
        estimate_plan(c, conf, out)
    kids = [out[id(c)] for c in plan.children]
    bytes_out = estimate_bytes(plan)
    # Bytes flowing INTO this node = children's outputs (leaf nodes read
    # their own bytes). Unknown child bytes poison the subtree estimate.
    if plan.children:
        child_out = [k.bytes_out for k in kids]
        bytes_in = None if any(b is None for b in child_out) \
            else sum(child_out)
    else:
        bytes_in = bytes_out
    # ROLLUP/CUBE expand the input once per grouping set before the
    # partial aggregate — both engines pay the multiplication.
    mult = 1
    if isinstance(plan, L.LogicalAggregate) and plan.grouping is not None:
        nk = len(plan.group_by)
        mult = (nk + 1) if plan.grouping == "rollup" else (1 << nk)
    sync_ms = effective_sync_floor_ms(conf)
    dev_bw = max(effective_device_gbps(conf), 1e-3) * 1e9 / 1e3
    host_bw = max(float(conf.get(C.COST_HOST_GBPS)), 1e-3) * 1e9 / 1e3
    syncs = _node_syncs(plan, conf)
    if bytes_in is None:
        # Unknown size: charge only the sync floor on the device side and
        # a token host pass — the placement step refuses to host-place a
        # subtree whose bytes are unknown anyway.
        dev_node_ms = syncs * sync_ms
        host_node_ms = 0.5
        subtree_bytes = None
    else:
        moved = bytes_in * mult
        dev_node_ms = syncs * sync_ms + moved / dev_bw
        host_node_ms = 0.5 + moved / host_bw
        kid_bytes = [k.subtree_bytes for k in kids]
        subtree_bytes = None if any(b is None for b in kid_bytes) \
            else max([moved] + kid_bytes) if kids else moved
    out[id(plan)] = NodeEstimate(
        name=plan.name,
        bytes_out=bytes_out,
        subtree_bytes=subtree_bytes,
        device_ms=sum(k.device_ms for k in kids) + dev_node_ms,
        host_ms=sum(k.host_ms for k in kids) + host_node_ms,
        syncs=sum(k.syncs for k in kids) + syncs)
    return out


# ---------------------------------------------------------------------------
# Placement
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CostReport:
    """What the model decided, for explain / Cost@query metrics."""

    skipped: Optional[str] = None          # gate that disabled placement
    placements: int = 0                    # host-placed subtree roots
    nodes_host_placed: int = 0             # nodes inside those subtrees
    est_device_ms: float = 0.0             # root subtree estimates
    est_host_ms: float = 0.0
    est_syncs: int = 0
    lines: List[str] = dataclasses.field(default_factory=list)

    def explain_lines(self) -> List[str]:
        if self.skipped is not None:
            return [f"Cost model: skipped ({self.skipped})"]
        head = (f"Cost model: {self.placements} host placement(s); root "
                f"estimate device {self.est_device_ms:.0f}ms "
                f"({self.est_syncs} syncs) vs host "
                f"{self.est_host_ms:.0f}ms")
        return [head] + [f"  {ln}" for ln in self.lines]


def _mark_host(meta) -> int:
    """Flip one whole subtree to the host engine; returns nodes marked."""
    meta.cost_host = True
    return 1 + sum(_mark_host(c) for c in meta.children)


def apply_placement(meta, conf: "C.TpuConf") -> CostReport:
    """Top-down maximal-subtree placement over the tagged meta tree.

    A subtree is host-placed when its estimate is known, its bytes fit
    the ``cost.maxHostBytes`` ceiling, and the host estimate strictly
    beats the device estimate (ties keep the device — the device's
    numbers only improve as inputs grow). Children of a host-placed
    subtree are not revisited: the placement is maximal by construction.
    """
    report = CostReport()
    report.skipped = _placement_gates(conf, meta.plan)
    _record("costPlanningRuns")
    if report.skipped is not None:
        return report
    ests = estimate_plan(meta.plan, conf)
    max_host = int(conf.get(C.COST_MAX_HOST_BYTES))
    explain = bool(conf.get(C.COST_EXPLAIN)) or \
        conf.explain in ("ALL", "NOT_ON_GPU")
    root_est = ests[id(meta.plan)]
    report.est_device_ms = root_est.device_ms
    report.est_host_ms = root_est.host_ms
    report.est_syncs = root_est.syncs

    def walk(m, depth: int):
        est = ests[id(m.plan)]
        placeable = m.on_device and est.subtree_bytes is not None and \
            est.subtree_bytes <= max_host and est.host_ms < est.device_ms
        if explain:
            b = "?" if est.bytes_out is None else f"{est.bytes_out:,}"
            report.lines.append(
                "  " * depth + f"{m.plan.name}: ~{b} bytes, device "
                f"{est.device_ms:.0f}ms/{est.syncs} syncs, host "
                f"{est.host_ms:.0f}ms"
                + (" -> HOST" if placeable else ""))
        if placeable:
            report.placements += 1
            report.nodes_host_placed += _mark_host(m)
            m.notes.append(
                f"cost model: host placement (est device "
                f"{est.device_ms:.0f}ms incl {est.syncs} syncs > host "
                f"{est.host_ms:.0f}ms over ~{est.subtree_bytes:,} bytes)")
            return                 # maximal subtree: stop descending
        for c in m.children:
            walk(c, depth + 1)

    walk(meta, 0)
    if report.placements:
        _record("costHostPlacements", report.placements)
        _record("costHostPlacedNodes", report.nodes_host_placed)
    return report
