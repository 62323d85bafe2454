"""Spill-tier tests (ref: RapidsDeviceMemoryStoreSuite,
RapidsHostMemoryStoreSuite, RapidsDiskStoreSuite, RapidsBufferCatalogSuite,
GpuSemaphoreSuite)."""

import threading
import time

import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.host import HostBatch, host_to_device, \
    device_to_host
from spark_rapids_tpu.memory import (
    PRIORITY_ACTIVE_INPUT, PRIORITY_DEFAULT, PRIORITY_SHUFFLE_OUTPUT,
    BufferCatalog, SpillableBatch, StorageTier, TpuSemaphore)
from spark_rapids_tpu.memory.native import (
    NativeSpillFile, PySpillFile, load, open_spill_file)


def make_batch(seed, n=64):
    rng = np.random.default_rng(seed)
    hb = HostBatch.from_pydict(
        [("a", dt.INT64), ("s", dt.STRING)],
        {"a": rng.integers(0, 1000, n).tolist(),
         "s": [f"row{seed}_{i}" for i in range(n)]})
    return host_to_device(hb)


class TestNativeSpillFile:
    def test_native_lib_compiles(self):
        assert load() is not None, "g++ native spill store must build"

    def test_write_read_free(self, tmp_path):
        f = open_spill_file(str(tmp_path))
        assert isinstance(f, NativeSpillFile)
        b1 = f.write(b"hello world")
        b2 = f.write(b"x" * 4096)
        assert f.read(b1) == b"hello world"
        assert f.read(b2) == b"x" * 4096
        assert f.allocated_bytes == 11 + 4096
        f.free(b1)
        assert f.allocated_bytes == 4096
        # Freed range is reused (first-fit): write something smaller.
        b3 = f.write(b"abc")
        assert f.read(b3) == b"abc"
        assert f.file_bytes == 11 + 4096   # no growth
        f.close()

    def test_python_fallback_equivalent(self, tmp_path):
        f = PySpillFile(str(tmp_path))
        b1 = f.write(b"data1")
        assert f.read(b1) == b"data1"
        f.free(b1)
        f.close()


class TestCatalogSpill:
    def test_device_to_host_spill_on_budget(self, tmp_path):
        b = make_batch(1)
        size = b.device_size_bytes()
        cat = BufferCatalog(device_budget_bytes=int(size * 2.5),
                            host_budget_bytes=1 << 30,
                            spill_dir=str(tmp_path))
        ids = [cat.add_batch(make_batch(i)) for i in range(3)]
        # Third add must have pushed the first (lowest id) to host.
        assert cat.tier_of(ids[0]) == StorageTier.HOST
        assert cat.tier_of(ids[2]) == StorageTier.DEVICE
        assert cat.metrics["spill_to_host"] >= 1
        # Re-acquire: comes back to device, bit-identical.
        restored = cat.acquire_batch(ids[0])
        assert cat.tier_of(ids[0]) == StorageTier.DEVICE
        orig = device_to_host(make_batch(0)).to_pylist()
        assert device_to_host(restored).to_pylist() == orig
        cat.close()

    def test_cascade_to_disk_and_restore(self, tmp_path):
        b = make_batch(0)
        size = b.device_size_bytes()
        cat = BufferCatalog(device_budget_bytes=int(size * 1.5),
                            host_budget_bytes=int(size * 1.5),
                            spill_dir=str(tmp_path))
        ids = [cat.add_batch(make_batch(i)) for i in range(4)]
        tiers = [cat.tier_of(i) for i in ids]
        assert StorageTier.DISK in tiers
        assert cat.metrics["spill_to_disk"] >= 1
        disk_id = ids[tiers.index(StorageTier.DISK)]
        seed = ids.index(disk_id)
        restored = cat.acquire_batch(disk_id)
        expect = device_to_host(make_batch(seed)).to_pylist()
        assert device_to_host(restored).to_pylist() == expect
        assert cat.metrics["restore_from_disk"] == 1
        cat.close()

    def test_priorities_shuffle_spills_first(self, tmp_path):
        b = make_batch(0)
        size = b.device_size_bytes()
        cat = BufferCatalog(device_budget_bytes=int(size * 2.5),
                            spill_dir=str(tmp_path))
        keep = cat.add_batch(make_batch(1), PRIORITY_DEFAULT)
        shuffle = cat.add_batch(make_batch(2), PRIORITY_SHUFFLE_OUTPUT)
        cat.add_batch(make_batch(3))   # forces one spill
        assert cat.tier_of(shuffle) == StorageTier.HOST
        assert cat.tier_of(keep) == StorageTier.DEVICE
        cat.close()

    def test_active_input_never_spills(self, tmp_path):
        b = make_batch(0)
        size = b.device_size_bytes()
        cat = BufferCatalog(device_budget_bytes=int(size * 1.5),
                            spill_dir=str(tmp_path))
        active = cat.add_batch(make_batch(1), PRIORITY_ACTIVE_INPUT)
        cat.add_batch(make_batch(2))
        cat.add_batch(make_batch(3))
        assert cat.tier_of(active) == StorageTier.DEVICE
        cat.close()

    def test_spillable_batch_handle(self, tmp_path):
        cat = BufferCatalog(spill_dir=str(tmp_path))
        sb = SpillableBatch(cat, make_batch(5))
        with sb as batch:
            assert int(batch.num_rows) == 64
        sb.close()
        cat.close()


class TestSemaphore:
    def test_limits_concurrency(self):
        sem = TpuSemaphore(2)
        active = []
        peak = []
        lock = threading.Lock()

        def task():
            with sem:
                with lock:
                    active.append(1)
                    peak.append(len(active))
                time.sleep(0.02)
                with lock:
                    active.pop()

        threads = [threading.Thread(target=task) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert max(peak) <= 2


# What the attached v5e raised when a device_put found no room
# (chip run, PR 21) ...
_V5E_RUNTIME_OOM = (
    "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
    "allocate 3.00G. That was not possible. There are 693.97M free.; "
    "(0x0x0_HBM0)")
# ... and what its compiler says of programs that do not fit HBM or a
# kernel's fast memory (described v5e:2x2 topology, PR 21).
_V5E_COMPILE_REFUSALS = [
    "RESOURCE_EXHAUSTED: Allocation (size=68719476736) would exceed "
    "memory (size=17179869184) :: #allocation7 [shape = "
    "'f32[131072,1024,128]{1,0,2:T(8,128)}', space=hbm, size = "
    "0xffffffffffffffff, tag = 'output of fusion@{}']",
    "RESOURCE_EXHAUSTED: Allocation (size=268435456) would exceed memory "
    "(size=134217728) :: #allocation2 [shape = 'u8[268435456]{0}', "
    "space=vmem, size = 0x10000000, tag = 'input window allocation for "
    "operator input 0.']",
    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
    "memory in memory space hbm. Used 20.1G of 15.75G hbm.",
    "RESOURCE_EXHAUSTED",
]


class TestOomRetry:
    """OOM -> spill -> retry (DeviceMemoryEventHandler.scala:42-69
    analog, memory/oom.py): a RESOURCE_EXHAUSTED dispatch spills every
    spillable catalog buffer and re-runs the dispatch once."""

    def test_retry_after_spill(self, tmp_path):
        from spark_rapids_tpu.memory.oom import (retry_on_oom,
                                                 set_active_catalog)
        cat = BufferCatalog(device_budget_bytes=1 << 30,
                            spill_dir=str(tmp_path))
        bid = cat.add_batch(make_batch(1))
        cat.release(bid)
        set_active_catalog(cat)
        try:
            calls = []

            def flaky():
                calls.append(1)
                if len(calls) == 1:
                    raise RuntimeError(
                        "RESOURCE_EXHAUSTED: Out of memory allocating "
                        "12345 bytes")
                return "ok"

            assert retry_on_oom(flaky) == "ok"
            assert len(calls) == 2
            assert cat._entries[bid].tier == StorageTier.HOST
            assert cat.metrics.get("oom_spills") == 1
            # The spilled batch restores transparently.
            back = device_to_host(cat.acquire_batch(bid), ("a", "s"))
            assert back.num_rows == 64
        finally:
            set_active_catalog(None)

    def test_runtime_allocation_failure_is_oom(self):
        from spark_rapids_tpu.memory.oom import is_oom_error
        assert is_oom_error(RuntimeError(_V5E_RUNTIME_OOM))

    @pytest.mark.parametrize("msg", _V5E_COMPILE_REFUSALS)
    def test_compile_time_resource_exhausted_is_not_oom(self, msg):
        """A program that does not fit is not memory pressure: it must
        propagate, not walk the spill ladder down to the host engine."""
        from spark_rapids_tpu.memory.oom import is_oom_error, retry_on_oom
        assert not is_oom_error(RuntimeError(msg))
        calls = []

        def refused():
            calls.append(1)
            raise RuntimeError(msg)

        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            retry_on_oom(refused)
        assert len(calls) == 1

    def test_non_oom_propagates(self):
        from spark_rapids_tpu.memory.oom import retry_on_oom

        def bad():
            raise ValueError("boom")

        with pytest.raises(ValueError):
            retry_on_oom(bad)

    def test_oom_with_nothing_spillable_reraises(self, tmp_path):
        from spark_rapids_tpu.memory.oom import (retry_on_oom,
                                                 set_active_catalog)
        cat = BufferCatalog(device_budget_bytes=1 << 30,
                            spill_dir=str(tmp_path))
        set_active_catalog(cat)
        try:
            def oom():
                raise RuntimeError(_V5E_RUNTIME_OOM)

            with pytest.raises(RuntimeError):
                retry_on_oom(oom)
        finally:
            set_active_catalog(None)
