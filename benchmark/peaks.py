"""Published chip peaks, keyed by ``jax.devices()[0].device_kind``
(the benchmark's own copy of ``spark_rapids_tpu/benchmarks/peaks.py``,
PR 24: a later PR cannot move what a roofline share divides by).

The one table every utilization or roofline figure in this repo divides
by. A device that is not here is an error, not a default: a ratio
against another chip's peak is a wrong number with a real name.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    # Google Cloud documentation, "TPU v5e" (one chip).
    "TPU v5 lite": {
        "hbm_gb_per_sec": 819.0,
        "hbm_gb": 16.0,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str) -> Dict[str, object]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks recorded for device kind "
            f"{device_kind!r}; add it to benchmark/peaks.py with its "
            f"source (known: {sorted(PEAKS)})") from None
