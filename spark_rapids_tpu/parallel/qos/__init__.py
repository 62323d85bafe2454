"""Serving QoS subsystem (ISSUE 14): cost-aware scheduling for the
multi-query admission layer.

Replaces the QueryManager's FIFO run queue — when enabled — with:

- **Priority classes** ``interactive`` / ``batch`` / ``background``
  (per query via conf or the ``priority=`` kwarg of
  ``DataFrame.collect/submit``), drained by weighted fair queueing
  with a configurable weight vector and a HARD starvation bound
  (policy.py).
- **Shortest-job-first within a class** using the plan/cost.py
  estimate; plan-cache hits reuse the template's CostReport so the
  ordering key is free for repeat shapes.
- **Per-tenant quotas** — in-flight query caps, owner-tagged catalog
  bytes, kernel-cache compile budgets (quotas.py).
- **Deadline-aware admission** — a query whose estimate cannot meet
  its ``timeout_ms`` deadline is rejected at admit time (admission.py).

Default OFF: ``spark.rapids.sql.scheduler.qos.enabled`` (conf wins) or
``SRT_QOS=1`` (env, the CI matrix hook). Disabled, the QueryManager's
FIFO path is byte-for-byte the pre-QoS scheduler — the ``qos-on``
tier-1 matrix entry proves the whole suite passes identically with the
subsystem live.

See docs/serving.md for the model and the 1000-query soak contract.
"""

from __future__ import annotations

import os
import threading
from typing import Dict

from spark_rapids_tpu.parallel.qos.admission import QosPolicy
from spark_rapids_tpu.parallel.qos.policy import (CLASS_RANK, CLASSES,
                                                  DEFAULT_CLASS, WfqQueue,
                                                  parse_weights,
                                                  resolve_class)
from spark_rapids_tpu.parallel.qos.quotas import (DEFAULT_TENANT,
                                                  TenantQuotas,
                                                  resolve_tenant)

__all__ = [
    "CLASSES", "CLASS_RANK", "DEFAULT_CLASS", "DEFAULT_TENANT",
    "QosPolicy", "TenantQuotas", "WfqQueue", "counters", "parse_weights",
    "qos_enabled", "reset_counters", "resolve_class", "resolve_tenant",
]

_COUNTER_LOCK = threading.Lock()
_COUNTERS: Dict[str, float] = {}


def _record(name: str, amount: float = 1) -> None:
    with _COUNTER_LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + amount


def counters() -> Dict[str, float]:
    """Process-global QoS counters:
    per-class admissions (``admitted.<class>``), rejections by kind
    (``rejected.queue-full`` / ``rejected.tenant-quota`` /
    ``rejected.deadline-unmeetable`` / ``rejected.admission-timeout``),
    ``starvationBoundEngagements``, ``quotaEvictions``, and per-tenant
    plan-cache outcomes (``planCacheHit.<tenant>`` /
    ``planCacheMiss.<tenant>``)."""
    with _COUNTER_LOCK:
        return dict(_COUNTERS)


def reset_counters() -> None:
    with _COUNTER_LOCK:
        _COUNTERS.clear()


def qos_enabled(conf=None) -> bool:
    """Conf key wins; else the SRT_QOS env (CI matrix hook); else the
    registered default (False) — the cost_enabled/plan_cache_enabled
    gate pattern."""
    from spark_rapids_tpu import config as C
    if conf is not None and conf.raw.get(C.QOS_ENABLED.key) is not None:
        return bool(conf.get(C.QOS_ENABLED))
    env = os.environ.get("SRT_QOS")
    if env is not None:
        return env.strip() not in ("", "0", "false", "no")
    return bool(C.QOS_ENABLED.default)
