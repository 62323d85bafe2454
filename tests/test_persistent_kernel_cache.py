"""Persistent compilation cache (ISSUE 4 satellite, re-ruled in PR 21).

Exactly one rule picks the directory: ``JAX_COMPILATION_CACHE_DIR`` when
set, else ``<checkout>/.jax_cache`` (package import applies it).
``spark.rapids.sql.kernelCache.persistentDir`` may move it only while the
variable is unset. Compiled executables serialize there and are served
back (persistentCacheHits) after the in-memory caches are dropped — the
in-process proxy for surviving a process restart.

JAX's compilation-cache dir is process-global — a test that moved it
would send every later compile of the pytest process elsewhere. The
scenarios that move it therefore run in a throwaway subprocess; only
side-effect-free pieces run in-process.
"""

import os
import subprocess
import sys

import jax

from spark_rapids_tpu.ops import kernel_cache as kc


def test_empty_dir_changes_nothing():
    """An empty key neither enables nor moves anything: the directory
    stays where package import put it, and the stats name that one."""
    before = jax.config.jax_compilation_cache_dir
    assert not kc.configure_persistent("")
    assert not kc.configure_persistent(None)
    assert jax.config.jax_compilation_cache_dir == before
    assert kc.persistent_stats()["dir"] == (before or None)


def _run(body: str, cache_dir_env=None):
    """Run ``body`` in a fresh interpreter with
    ``JAX_COMPILATION_CACHE_DIR`` set to ``cache_dir_env`` or unset."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir_env:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir_env
    return subprocess.run(
        [sys.executable, "-c", body],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=300)


_ENV_WINS_BODY = r"""
import os, tempfile
import jax
import spark_rapids_tpu
from spark_rapids_tpu.ops import kernel_cache as kc
env_dir = os.environ["JAX_COMPILATION_CACHE_DIR"]
assert jax.config.jax_compilation_cache_dir == env_dir
other = tempfile.mkdtemp()
assert not kc.configure_persistent(other), "moved the cache off the env dir"
assert jax.config.jax_compilation_cache_dir == env_dir
assert kc.configure_persistent(env_dir), "conf naming the env dir reports it"
# ... and through the session conf, the way a user would set it.
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.plan.logical import col
s = TpuSession()
s.set("spark.rapids.sql.kernelCache.persistentDir", other)
df = s.create_dataframe({"a": [1, 2, 3]}, (("a", dt.INT64),))
assert df.select((col("a") * 2).alias("b")).collect() == [(2,), (4,), (6,)]
assert jax.config.jax_compilation_cache_dir == env_dir
assert kc.persistent_stats()["dir"] == env_dir
assert not os.listdir(other), "something was cached outside the env dir"
print("ENV_DIR_KEPT")
"""


def test_env_dir_is_never_overridden(tmp_path):
    out = _run(_ENV_WINS_BODY, str(tmp_path))
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "ENV_DIR_KEPT" in out.stdout


_DEFAULT_DIR_BODY = r"""
import os
import jax
import spark_rapids_tpu
root = os.path.dirname(os.path.dirname(os.path.abspath(spark_rapids_tpu.__file__)))
assert jax.config.jax_compilation_cache_dir == os.path.join(root, ".jax_cache"), \
    jax.config.jax_compilation_cache_dir
print("DEFAULT_IN_CHECKOUT")
"""


def test_default_dir_is_fixed_path_in_checkout():
    out = _run(_DEFAULT_DIR_BODY)
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "DEFAULT_IN_CHECKOUT" in out.stdout


_SUBPROCESS_BODY = r"""
import glob, os, sys, tempfile
import jax
import jax.numpy as jnp
from spark_rapids_tpu.ops import kernel_cache as kc

d = tempfile.mkdtemp()
# Compile BEFORE enabling: proves configure_persistent resets jax's
# "cache usable" latch instead of requiring process-start configuration.
jax.jit(lambda x: x + 1)(jnp.arange(4)).block_until_ready()

assert kc.configure_persistent(d), "enable failed"
assert kc.configure_persistent(d), "not idempotent"
s = kc.cache().stats()
assert s.get("persistentCacheDir") == d, s
assert "persistentCacheHits" in s and "persistentCacheMisses" in s, s

f = jax.jit(lambda x: x * 3 + 1)
f(jnp.arange(16)).block_until_ready()
files = glob.glob(os.path.join(d, "*"))
assert files, "persistent cache wrote nothing"

before = kc.persistent_stats()["hits"]
# Drop jax's in-memory executable caches: the SAME computation must now
# come back from disk (what a restarted process would do).
jax.clear_caches()
g = jax.jit(lambda x: x * 3 + 1)
g(jnp.arange(16)).block_until_ready()
after = kc.persistent_stats()["hits"]
assert after > before, (before, after)

# The session conf wires through the planner.
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.plan.logical import col
s = TpuSession()
s.set("spark.rapids.sql.kernelCache.persistentDir", d)
df = s.create_dataframe({"a": [1, 2, 3]}, (("a", dt.INT64),))
assert df.select((col("a") * 2).alias("b")).collect() == \
    [(2,), (4,), (6,)]
assert kc.persistent_stats()["dir"] == d
print("PERSISTENT_CACHE_OK")
"""


def test_enable_write_and_hit_in_subprocess():
    # Bounded (~20s: one jax import + a handful of tiny compiles) and
    # fully isolated — the moved global cache dies with the subprocess.
    # The conf key may move the cache only with the env variable unset.
    out = _run(_SUBPROCESS_BODY)
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "PERSISTENT_CACHE_OK" in out.stdout
