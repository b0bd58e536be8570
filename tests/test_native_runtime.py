"""Native runtime: recordio format, blocking queue, buddy allocator,
threaded prefetch reader — C++ components bound via ctypes, interoperable
with the pure-python fallback format.
"""

import pickle
import threading

import numpy as np
import pytest

from paddle_tpu import native, recordio


def test_native_builds():
    assert native.available(), "g++ toolchain present: native must build"


def test_build_removes_libraries_of_earlier_sources(tmp_path, monkeypatch):
    """The library's name carries a hash of native.cc, so every edit leaves
    the previous build behind: a successful build sweeps them out."""
    import subprocess

    stale = [tmp_path / "libpaddle_tpu_native.0123456789abcdef.so",
             tmp_path / "libpaddle_tpu_native.so"]
    for f in stale:
        f.write_bytes(b"old")
    keep = tmp_path / "native.cc"
    keep.write_text("// source")
    monkeypatch.setattr(native, "_HERE", str(tmp_path))

    def fake_gpp(cmd, **kw):
        with open(cmd[cmd.index("-o") + 1], "wb") as f:
            f.write(b"new")
    monkeypatch.setattr(subprocess, "run", fake_gpp)
    lib = tmp_path / "libpaddle_tpu_native.fedcba9876543210.so"
    native._build(str(lib))
    assert sorted(p.name for p in tmp_path.iterdir()) == \
        sorted([lib.name, keep.name])
    assert lib.read_bytes() == b"new"


def test_recordio_roundtrip_native(tmp_path):
    path = str(tmp_path / "data.recordio")
    records = [b"hello", b"", b"x" * 100000, pickle.dumps({"a": 1})]
    w = recordio.writer(path)
    for r in records:
        w.write(r)
    w.close()
    assert recordio.read_all(path) == records


def test_recordio_native_python_interop(tmp_path):
    """Files written natively parse with the python scanner and vice versa
    (same on-disk format)."""
    recs = [b"r%d" % i for i in range(1000)]
    p1 = str(tmp_path / "native.recordio")
    w = recordio._NativeWriter(p1)
    for r in recs:
        w.write(r)
    w.close()
    s = recordio._PyScanner(p1)
    got = []
    while True:
        r = s.read()
        if r is None:
            break
        got.append(r)
    assert got == recs

    p2 = str(tmp_path / "py.recordio")
    w = recordio._PyWriter(p2)
    for r in recs:
        w.write(r)
    w.close()
    s = recordio._NativeScanner(p2)
    got = []
    while True:
        r = s.read()
        if r is None:
            break
        got.append(r)
    assert got == recs


def test_recordio_detects_corruption(tmp_path):
    path = str(tmp_path / "corrupt.recordio")
    w = recordio.writer(path)
    w.write(b"payload" * 100)
    w.close()
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF  # flip a payload bit
    open(path, "wb").write(bytes(blob))
    with pytest.raises(IOError, match="CRC|corrupt"):
        recordio.read_all(path)


def test_blocking_queue_bounded_and_ordered():
    q = native.BlockingQueue(capacity=4)
    items = [b"item%d" % i for i in range(100)]
    got = []

    def consumer():
        while True:
            try:
                got.append(q.pop())
            except EOFError:
                return

    t = threading.Thread(target=consumer)
    t.start()
    for it in items:
        q.push(it)
    q.close()
    t.join(timeout=10)
    assert got == items


def test_blocking_queue_timeout():
    q = native.BlockingQueue(capacity=1)
    assert q.pop(timeout_ms=50) is None  # empty → timeout
    q.push(b"a")
    assert not q.push(b"b", timeout_ms=50)  # full → timeout returns False


def test_buddy_allocator_split_merge():
    arena = native.BuddyAllocator(1 << 16, min_block=64)
    a = arena.alloc(100)    # rounds to 128
    b = arena.alloc(64)
    c = arena.alloc(4000)   # rounds to 4096
    assert a and b and c
    assert arena.in_use == 128 + 64 + 4096
    arena.free(b)
    arena.free(a)
    arena.free(c)
    assert arena.in_use == 0
    # after full coalescing one max-size alloc must fit again
    big = arena.alloc(1 << 16)
    assert big
    arena.free(big)
    # exhaustion returns None, not a crash
    huge = arena.alloc(1 << 20)
    assert huge is None
    with pytest.raises(ValueError):
        arena.free(12345)  # bogus pointer


def test_buddy_allocator_tiny_arena():
    # arena smaller than min_block must round up, not corrupt memory
    arena = native.BuddyAllocator(32)
    p = arena.alloc(16)
    assert p
    arena.free(p)
    assert arena.in_use == 0


def test_recordio_detects_truncation(tmp_path):
    path = str(tmp_path / "trunc.recordio")
    w = recordio.writer(path)
    for i in range(100):
        w.write(b"record-%03d" % i)
    w.close()
    blob = open(path, "rb").read()
    open(path, "wb").write(blob[:len(blob) - 7])  # chop mid-chunk
    with pytest.raises(IOError, match="CRC|corrupt"):
        recordio.read_all(path)


def test_prefetch_reader_over_shards(tmp_path):
    shards = []
    expect = set()
    for s in range(4):
        p = str(tmp_path / ("shard%d.recordio" % s))
        w = recordio.writer(p)
        for i in range(50):
            rec = b"s%d-r%d" % (s, i)
            w.write(rec)
            expect.add(rec)
        w.close()
        shards.append(p)
    gen = recordio.reader(shards, n_threads=3, capacity=16)
    got = list(gen())
    assert set(got) == expect
    assert len(got) == len(expect)


def test_data_pipeline_via_recordio(tmp_path):
    """End-to-end: numpy batches through recordio into a training feed."""
    path = str(tmp_path / "batches.recordio")
    rng = np.random.RandomState(0)
    batches = [rng.randn(8, 4).astype(np.float32) for _ in range(10)]
    with recordio.open_writer(path) as w:
        for b in batches:
            w.write(pickle.dumps(b))
    out = [pickle.loads(r) for r in recordio.read_all(path)]
    assert len(out) == 10
    for a, b in zip(batches, out):
        np.testing.assert_array_equal(a, b)


def _both_scanners():
    # exercise the python and native scanners explicitly: they must agree
    # on what counts as corruption (ADVICE r1: they disagreed on truncated
    # headers, and the native scanner over-read on header bit flips)
    out = [recordio._PyScanner]
    if native.available():
        out.append(recordio._NativeScanner)
    return out


def _drain(scanner_cls, path):
    s = scanner_cls(path)
    try:
        recs = []
        while True:
            r = s.read()
            if r is None:
                return recs
            recs.append(r)
    finally:
        s.close()


@pytest.mark.parametrize("scanner_cls", _both_scanners())
def test_recordio_header_bitflip_is_corruption(tmp_path, scanner_cls):
    # the chunk CRC covers only the payload: a flipped num_records in the
    # header passes magic+CRC and must be caught by record-walk bounds
    # checks, not read past the chunk buffer
    path = str(tmp_path / "hdr.recordio")
    w = recordio.writer(path, compress=False)
    for i in range(4):
        w.write(b"rec-%d" % i)
    w.close()
    blob = bytearray(open(path, "rb").read())
    n_records = int.from_bytes(blob[4:8], "little")
    blob[4:8] = (n_records + 1000).to_bytes(4, "little")
    open(path, "wb").write(bytes(blob))
    with pytest.raises(IOError, match="overrun|corrupt"):
        _drain(scanner_cls, path)


@pytest.mark.parametrize("scanner_cls", _both_scanners())
def test_recordio_partial_trailing_header_is_corruption(tmp_path, scanner_cls):
    path = str(tmp_path / "partial.recordio")
    w = recordio.writer(path)
    w.write(b"whole chunk")
    w.close()
    with open(path, "ab") as f:
        f.write(b"\x73\x74\x66\x01junk")  # 8 bytes: magic + garbage
    with pytest.raises(IOError, match="truncated|corrupt"):
        _drain(scanner_cls, path)


@pytest.mark.skipif(not native.available(), reason="needs native lib")
def test_prefetch_reader_surfaces_corruption(tmp_path):
    good = str(tmp_path / "good.recordio")
    bad = str(tmp_path / "bad.recordio")
    for p in (good, bad):
        w = recordio.writer(p, compress=False)
        for i in range(4):
            w.write(b"rec-%d" % i)
        w.close()
    blob = bytearray(open(bad, "rb").read())
    n_records = int.from_bytes(blob[4:8], "little")
    blob[4:8] = (n_records + 1000).to_bytes(4, "little")
    open(bad, "wb").write(bytes(blob))
    with pytest.raises(IOError, match="corrupt"):
        list(recordio.reader([good, bad], n_threads=1)())


def test_multislot_native_parser_parity():
    """Native multislot_parse_line == the python fallback, including
    malformed-line rejection."""
    import ctypes
    from paddle_tpu import native
    if not native.available():
        import pytest
        pytest.skip("no native toolchain")
    from paddle_tpu.fluid.dataset import InMemoryDataset
    ds = InMemoryDataset()
    spec = [("f", "float32", None), ("ids", "int64", None),
            ("lbl", "int64", 1)]
    line = "3 0.5 -1.25 3e2 2 11 12 1 4"
    native_fn = ds._native_parser(spec)
    assert native_fn is not None
    got = native_fn(line)
    import numpy as np
    np.testing.assert_allclose(got["f"],
                               np.array([0.5, -1.25, 300.0], np.float32))
    np.testing.assert_array_equal(got["ids"], [11, 12])
    np.testing.assert_array_equal(got["lbl"], [4])
    import pytest
    with pytest.raises(ValueError):
        native_fn("3 0.5")                       # truncated
    with pytest.raises(ValueError):
        native_fn("3 0.5 1.0 2.0 2 7 8 2 4 5")   # dense slot wrong arity


def test_multislot_native_parser_malformed_count_and_wrap():
    """Review regressions: '2.5' counts rejected; 2^32+k counts don't
    wrap past the cap; float64 spec falls back to python."""
    from paddle_tpu import native
    if not native.available():
        import pytest
        pytest.skip("no native toolchain")
    from paddle_tpu.fluid.dataset import InMemoryDataset
    import pytest
    ds = InMemoryDataset()
    spec = [("f", "float32", None)]
    fn = ds._native_parser(spec)
    assert fn is not None
    with pytest.raises(ValueError):
        fn("2.5 1.0 2.0")
    with pytest.raises(ValueError):
        fn("4294967396 " + " ".join(["1.0"] * 100))
    ds64 = InMemoryDataset()
    assert ds64._native_parser([("d", "float64", None)]) is None
