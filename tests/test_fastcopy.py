"""Native parallel staging copier: correctness + fallback contract."""

import mmap
import os
import shutil
import subprocess

import numpy as np
import pytest

from dlrover_tpu.common import fastcopy

_SOURCE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "native", "fastcopy", "fastcopy.cc",
)


@pytest.fixture(scope="module", autouse=True)
def built_library(tmp_path_factory):
    """The library compiled from the tree's source into a directory of
    this module's own.  ``native/build/`` is not in git: whether it is
    there depends on which test built it first (``test_timer.py`` runs
    cmake on all of ``native/``), and these tests used to pass or skip
    by that chance."""
    cxx = next(
        (c for c in ("c++", "g++", "clang++") if shutil.which(c)), None
    )
    if cxx is None:
        pytest.skip("no C++ compiler to build native/fastcopy with")
    out = str(tmp_path_factory.mktemp("fastcopy") / "libfastcopy.so")
    subprocess.run(
        [cxx, "-std=c++17", "-O2", "-shared", "-fPIC", _SOURCE, "-o", out,
         "-lpthread"],
        check=True, capture_output=True, timeout=300,
    )
    saved = (fastcopy._LIB_PATHS, fastcopy._lib, fastcopy._loaded)
    fastcopy._LIB_PATHS, fastcopy._lib, fastcopy._loaded = [out], None, False
    assert fastcopy.available()
    yield out
    fastcopy._LIB_PATHS, fastcopy._lib, fastcopy._loaded = saved


@pytest.fixture()
def small_threshold(monkeypatch):
    monkeypatch.setattr(fastcopy, "MIN_PARALLEL_BYTES", 1)


class TestFastcopy:
    def test_batch_copy_correct(self, small_threshold):
        buf = mmap.mmap(-1, 1 << 20)
        view = memoryview(buf)
        rng = np.random.default_rng(0)
        arrs = [
            rng.integers(0, 255, size, dtype=np.uint8).reshape(shape)
            for size, shape in (
                (4096, (64, 64)), (100, (100,)), (3 * 7 * 11, (3, 7, 11)),
            )
        ]
        placements = []
        offset = 16
        for arr in arrs:
            placements.append((offset, arr))
            offset += arr.nbytes
        assert fastcopy.copy_into(view, placements)
        for off, arr in placements:
            got = np.frombuffer(
                view[off : off + arr.nbytes], dtype=np.uint8
            )
            assert np.array_equal(got, arr.reshape(-1))
        # bytes outside the placements untouched
        assert bytes(view[0:16]) == b"\x00" * 16

    def test_small_batch_declined(self):
        buf = bytearray(1024)
        arr = np.arange(10, dtype=np.uint8)
        # under MIN_PARALLEL_BYTES: caller must use its fallback loop
        assert not fastcopy.copy_into(memoryview(buf), [(0, arr)])

    def test_non_contiguous_declined(self, small_threshold):
        buf = bytearray(1 << 12)
        arr = np.arange(100, dtype=np.uint8).reshape(10, 10)[:, ::2]
        assert not arr.flags["C_CONTIGUOUS"]
        assert not fastcopy.copy_into(memoryview(buf), [(0, arr)])

    def test_empty_placements(self):
        assert not fastcopy.copy_into(memoryview(bytearray(8)), [])

    def test_snapshot_roundtrip_through_parallel_path(
        self, small_threshold, monkeypatch
    ):
        """write_snapshot -> read back, with the parallel copier forced on
        for every size: the wire format must be identical to the Python
        loop's."""
        from dlrover_tpu.common.multi_process import SharedMemoryBuffer
        from dlrover_tpu.trainer.flash_checkpoint import snapshot as snap

        shm = SharedMemoryBuffer(f"fastcopy-test-{id(self)}")
        try:
            leaves = [
                {
                    "path": "params/w",
                    "dtype": "float32",
                    "gshape": [8, 4],
                    "shards": [{
                        "index": [[0, 8], [0, 4]],
                        "data": np.arange(32, dtype=np.float32).reshape(
                            8, 4
                        ),
                    }],
                }
            ]
            snap.write_snapshot(shm, step=7, leaves=leaves,
                                extras={"k": 1})
            meta = snap.read_snapshot_meta(shm)
            assert meta["step"] == 7 and meta["extras"] == {"k": 1}
            shard_meta = meta["leaves"][0]["shards"][0]
            got = snap.read_shard_bytes(shm, meta, shard_meta, "float32")
            assert np.array_equal(
                got, np.arange(32, dtype=np.float32).reshape(8, 4)
            )
        finally:
            shm.unlink()
