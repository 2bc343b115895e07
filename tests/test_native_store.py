"""Native shared-memory arena store tests.

Covers the plasma-equivalent semantics (reference test model:
src/ray/object_manager/plasma/test/ + python/ray/tests/test_object_store*):
create/seal/get zero-copy, immutability dedupe, LRU eviction under
pressure, reader pins blocking eviction, crashed-reader pin reclamation,
multi-process access, and file overflow for oversized objects.
"""

import multiprocessing
import os

import numpy as np
import pytest

from ray_tpu._private import native_store
from ray_tpu._private.shm_store import FileObjectStore, layout_size, unpack

pytestmark = pytest.mark.skipif(not native_store.available(),
                                reason="native toolchain unavailable")


def make_store(tmp_path, capacity=1 << 22):
    return native_store.NativeShmObjectStore(str(tmp_path / "objects"),
                                             capacity=capacity)


def test_create_get_roundtrip(tmp_path):
    s = make_store(tmp_path)
    arr = np.arange(1000, dtype=np.float32)
    s.create("obj1", b"metameta", [memoryview(arr).cast("B")])
    meta, bufs = s.get("obj1")
    assert meta == b"metameta"
    out = np.frombuffer(bufs[0], dtype=np.float32)
    np.testing.assert_array_equal(out, arr)
    assert s.contains("obj1")
    assert s.get("missing") is None
    s.destroy()


def test_zero_copy_alignment(tmp_path):
    s = make_store(tmp_path)
    arr = np.arange(64, dtype=np.float64)
    s.create("a", b"", [memoryview(arr).cast("B")])
    _, bufs = s.get("a")
    # 64-byte aligned buffers so numpy views are aligned (shm_store layout)
    addr = np.frombuffer(bufs[0], dtype=np.float64).__array_interface__[
        "data"][0]
    assert addr % 64 == 0
    s.destroy()


def test_immutable_dedupe(tmp_path):
    s = make_store(tmp_path)
    s.put_raw("x", b"hello")
    s.put_raw("x", b"different")  # second create of same id is a no-op
    assert bytes(s.get_raw("x")) == b"hello"
    s.destroy()


def test_delete_and_list(tmp_path):
    s = make_store(tmp_path)
    for i in range(5):
        s.put_raw(f"o{i}", b"x" * 100)
    assert sorted(s.list_objects()) == [f"o{i}" for i in range(5)]
    assert s.delete("o2")
    assert not s.contains("o2")
    assert s.get("o2") is None
    assert sorted(s.list_objects()) == ["o0", "o1", "o3", "o4"]
    s.destroy()


def test_lru_eviction(tmp_path):
    s = make_store(tmp_path, capacity=1 << 20)  # 1 MiB arena
    blob = b"z" * (200 << 10)  # 200 KiB
    for i in range(10):  # 2 MB total: must evict
        s.put_raw(f"e{i}", blob)
        if i == 0:
            continue
        # touch e1 so it stays warm
        if s.contains("e1"):
            s.get_raw("e1")
    stats = s.stats()
    assert stats["num_evictions"] > 0
    # most recent object always present
    assert s.contains("e9")
    s.destroy()


def test_reader_pin_blocks_eviction(tmp_path):
    s = make_store(tmp_path, capacity=1 << 20)
    blob = b"p" * (300 << 10)
    s.put_raw("pinned", blob)
    held = s.get_raw("pinned")  # holds a pin via the mapping
    for i in range(8):
        s.put_raw(f"fill{i}", blob)
    assert s.contains("pinned")  # pinned object survived the pressure
    assert bytes(held[:5]) == b"ppppp"
    del held
    s.destroy()


def _child_reader(root, q):
    s = native_store.NativeShmObjectStore(root)
    data = s.get_raw("shared")
    q.put(bytes(data[:10]))
    s.close()


def test_multiprocess_get(tmp_path):
    s = make_store(tmp_path)
    s.put_raw("shared", b"0123456789abcdef")
    ctx = multiprocessing.get_context("fork")
    q = ctx.Queue()
    p = ctx.Process(target=_child_reader, args=(s.root, q))
    p.start()
    assert q.get(timeout=10) == b"0123456789"
    p.join(timeout=10)
    s.destroy()


def _child_crash_holding_pin(root):
    s = native_store.NativeShmObjectStore(root)
    s.get_raw("crashpin")
    os._exit(1)  # die without releasing


def test_crashed_reader_pin_reclaimed(tmp_path):
    s = make_store(tmp_path, capacity=1 << 20)
    s.put_raw("crashpin", b"c" * (300 << 10))
    ctx = multiprocessing.get_context("fork")
    p = ctx.Process(target=_child_crash_holding_pin, args=(s.root,))
    p.start()
    p.join(timeout=10)
    # dead pid's pin must not block eviction forever
    for i in range(8):
        s.put_raw(f"press{i}", b"q" * (300 << 10))
    assert not s.contains("crashpin")
    s.destroy()


def test_file_overflow(tmp_path):
    s = make_store(tmp_path, capacity=1 << 20)
    big = b"B" * (4 << 20)  # 4 MiB > 1 MiB arena
    s.put_raw("big", big)
    assert s.contains("big")
    assert bytes(s.get_raw("big")) == big
    assert isinstance(s._overflow, FileObjectStore)
    assert s.delete("big")
    s.destroy()


def test_read_write_bytes_transfer(tmp_path):
    """read_bytes/write_bytes (the inter-node transfer path) round-trips
    the packed layout between two stores."""
    s1 = make_store(tmp_path / "n1")
    s2 = make_store(tmp_path / "n2")
    arr = np.arange(256, dtype=np.int32)
    s1.create("t", b"m", [memoryview(arr).cast("B")])
    raw = s1.read_bytes("t")
    assert len(raw) == layout_size(1, [arr.nbytes])
    s2.write_bytes("t", raw)
    meta, bufs = s2.get("t")
    assert meta == b"m"
    np.testing.assert_array_equal(np.frombuffer(bufs[0], np.int32), arr)
    s1.destroy()
    s2.destroy()


def test_stats(tmp_path):
    s = make_store(tmp_path)
    s.put_raw("s1", b"x" * 10000)
    st = s.stats()
    assert st["num_objects"] == 1
    assert st["used"] >= 10000
    assert st["capacity"] > 0
    s.destroy()


def test_native_object_is_named_by_its_sources(tmp_path, monkeypatch):
    """The cached object carries the hash of what it was built from: a
    stale object that rode along in a copied tree is never the one asked
    for, whatever its mtime says, and an edit rebuilds."""
    import ctypes

    from ray_tpu.native import build

    monkeypatch.setattr(build, "_SRC_DIR", str(tmp_path))
    monkeypatch.setattr(build, "_BUILD_DIR", str(tmp_path / "_build"))
    src = tmp_path / "x.cc"
    src.write_text('extern "C" int v() { return 1; }\n')
    a = build.build_extension("x", ["x.cc"])
    assert ctypes.CDLL(a).v() == 1
    assert build.build_extension("x", ["x.cc"]) == a    # cached
    src.write_text('extern "C" int v() { return 2; }\n')
    os.utime(src, (0, 0))       # older than the object: mtime says fresh
    b = build.build_extension("x", ["x.cc"])
    assert b != a and ctypes.CDLL(b).v() == 2
    assert not os.path.exists(a)    # built from other sources: removed
