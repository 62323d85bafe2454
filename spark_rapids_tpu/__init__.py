"""spark-rapids-tpu: a TPU-native columnar SQL/ETL accelerator.

A brand-new framework with the capabilities of the RAPIDS Accelerator for Apache
Spark (reference: /root/reference, v0.3.0-SNAPSHOT), re-designed TPU-first on
JAX/XLA rather than ported from the CUDA/cuDF design:

- Columnar batches are pytrees of fixed-capacity HBM device arrays with a
  runtime row count, so everything compiles under ``jax.jit`` with static
  shapes (ref: GpuColumnVector.java's cuDF-backed batches, re-imagined for
  XLA's compilation model).
- Physical operators (scan, project, filter, hash aggregate, join, sort,
  window, ...) evaluate whole batches with jax.numpy kernels
  (ref: sql-plugin GpuExec nodes backed by libcudf JNI calls).
- The plan-rewrite layer keeps the reference's crown-jewel architecture:
  wrap -> tag -> convert with per-operator kill-switch configs, fallback
  reasons, and an ``explain`` report (ref: GpuOverrides.scala /
  RapidsMeta.scala), inserting explicit host<->device transitions.
- Shuffle is a planned collective exchange over the ICI mesh
  (jax.lax.all_to_all under shard_map) instead of a UCX peer-to-peer pull
  protocol (ref: shuffle-plugin/ucx/UCX.scala), with a host/disk spill tier.
"""

import os as _os

import jax as _jax

# Spark SQL semantics are 64-bit (LongType, DoubleType, TimestampType are all
# 8-byte); JAX's 32-bit default would silently truncate, so the engine
# requires x64 mode. On TPU, int64/float64 lower to emulated ops — the
# planner keeps hot paths in 32-bit/bfloat16 where Spark semantics allow.
_jax.config.update("jax_enable_x64", True)

# Persistent compilation cache: the TPU compiler takes seconds to minutes
# per program (64-bit types are emulated); the disk cache makes every
# shape/kernel a one-time cost across processes. The directory is
# JAX_COMPILATION_CACHE_DIR when set — then no code of this repo sets
# another (ops/kernel_cache.py configure_persistent) — else one fixed path
# in the checkout: the path is part of the cache key, so a directory that
# moves never hits.
if not _os.environ.get("SRT_NO_COMPILE_CACHE"):
    _default_cache = _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache")
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.environ.get("JAX_COMPILATION_CACHE_DIR", _default_cache))
    _jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)

from spark_rapids_tpu.version import __version__

from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.columnar.dtypes import (
    BOOL, INT8, INT16, INT32, INT64, FLOAT32, FLOAT64, DATE, TIMESTAMP, STRING,
    DataType,
)
from spark_rapids_tpu.columnar.batch import DeviceColumn, DeviceBatch
from spark_rapids_tpu.columnar.host import HostColumn, HostBatch

__all__ = [
    "__version__", "TpuConf", "DataType",
    "BOOL", "INT8", "INT16", "INT32", "INT64", "FLOAT32", "FLOAT64",
    "DATE", "TIMESTAMP", "STRING",
    "DeviceColumn", "DeviceBatch", "HostColumn", "HostBatch",
]
