"""Native host-path library tests: parity with the numpy fallback."""

import numpy as np
import pytest

from horovod_tpu.core import native


def test_native_builds_and_loads():
    assert native.available(), "native lib should build in this image"
    assert native.status() in ("built", "loaded")


def test_lib_is_keyed_on_source_content(tmp_path, monkeypatch):
    """A library built from other sources can never be the one that
    loads: the file name carries a hash of csrc/*.cpp, not an mtime
    comparison."""
    import os
    import shutil

    here = native._lib_path()
    assert os.path.exists(here)          # the loaded library's own name
    for name in native._SRC_NAMES:
        shutil.copy(os.path.join(native._SRC_DIR, name), tmp_path)
    monkeypatch.setattr(native, "_SRC_DIR", str(tmp_path))
    assert native._lib_path() == here    # same content, same library
    with open(tmp_path / "fusion.cpp", "a") as f:
        f.write("\n// edited\n")
    assert native._lib_path() != here


def test_pack_unpack_roundtrip():
    arrays = [np.random.rand(7).astype(np.float32),
              np.random.rand(3, 5).astype(np.float32).ravel(),
              np.random.rand(1).astype(np.float32)]
    sizes = [a.size for a in arrays]
    offsets_elems = np.cumsum([0] + sizes[:-1])
    offs_bytes = [int(o) * 4 for o in offsets_elems]
    total = sum(sizes)

    dst = np.empty(total, dtype=np.float32)
    native.pack(arrays, dst, offs_bytes)
    expected = np.concatenate([a.ravel() for a in arrays])
    np.testing.assert_array_equal(dst, expected)

    outs = [np.empty_like(a) for a in arrays]
    native.unpack(dst, outs, offs_bytes)
    for o, a in zip(outs, arrays):
        np.testing.assert_array_equal(o, a)


def test_pack_matches_numpy_fallback(monkeypatch):
    arrays = [np.random.rand(11).astype(np.float64) for _ in range(4)]
    offs = [int(o) * 8 for o in np.cumsum([0] + [11] * 3)]
    native_dst = np.empty(44, dtype=np.float64)
    native.pack(arrays, native_dst, offs)

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", True)
    fallback_dst = np.empty(44, dtype=np.float64)
    native.pack(arrays, fallback_dst, offs)
    np.testing.assert_array_equal(native_dst, fallback_dst)


def test_engine_uses_native_pack(hvd_shutdown):
    import horovod_tpu as hvd

    def fn():
        outs = hvd.grouped_allreduce(
            [np.full(5, float(hvd.rank()), np.float32),
             np.full((2, 3), 1.0, np.float32)], op=hvd.Sum)
        return outs

    results = hvd.run(fn, np=4)
    np.testing.assert_allclose(results[0][0], np.full(5, 6.0))
    np.testing.assert_allclose(results[0][1], np.full((2, 3), 4.0))


def test_pack_mt_matches_single():
    from horovod_tpu.core import native

    rs = np.random.RandomState(0)
    arrays = [rs.randn(n).astype(np.float32) for n in (7, 100, 3, 4096)]
    offsets, off = [], 0
    for a in arrays:
        offsets.append(off)
        off += a.nbytes
    total = off // 4
    a_mt = np.empty(total, np.float32)
    a_st = np.empty(total, np.float32)
    native.pack_mt(arrays, a_mt, offsets, nthreads=3)
    native.pack(arrays, a_st, offsets)
    np.testing.assert_array_equal(a_mt, a_st)


def test_arena_reuse_and_release():
    from horovod_tpu.core import native

    arena = native.Arena()
    a = arena.acquire(10_000, np.float32)
    assert a.shape == (2500,) and a.dtype == np.float32
    a[:] = 1.5
    addr = a.ctypes.data
    arena.release(a)
    # same size class comes back from the freelist (same slab)
    b = arena.acquire(9_000, np.float32)
    assert b.ctypes.data == addr
    arena.release(b)
    # growth is bounded by distinct size classes, not call count
    before = arena.total_bytes()
    for _ in range(20):
        c = arena.acquire(10_000)
        arena.release(c)
    assert arena.total_bytes() == before
    # double release is a no-op
    arena.release(b)


def test_native_timeline_writer(tmp_path):
    import json

    from horovod_tpu.utils.timeline import Timeline

    path = str(tmp_path / "tl.json")
    tl = Timeline(path)
    tl.negotiate_start("grad/layer_0", "ALLREDUCE")
    tl.op_start(["grad/layer_0"], "ALLREDUCE")
    tl.op_end()
    tl.close()
    events = json.load(open(path))
    names = [e["name"] for e in events]
    assert "thread_name" in names
    assert "NEGOTIATE_ALLREDUCE" in names
    assert "ALLREDUCE" in names
    phases = [e["ph"] for e in events if e["name"] == "ALLREDUCE"]
    assert phases == ["B", "E"]
    # name with JSON-hostile characters stays valid JSON
    path2 = str(tmp_path / "tl2.json")
    tl2 = Timeline(path2)
    tl2.op_start(['bad"name\\with\x01ctl'], "ALLREDUCE")
    tl2.op_end()
    tl2.close()
    events2 = json.load(open(path2))
    assert len(events2) >= 3
