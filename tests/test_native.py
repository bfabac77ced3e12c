"""Native C++ codec tests: must agree bit-for-bit with the pure-Python path."""

import shutil

import pytest

from tensorflowonspark_tpu import tfrecord

pytestmark = pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")


def native():
    from tensorflowonspark_tpu import native_bindings

    return native_bindings


def test_native_builds_and_loads():
    assert tfrecord.NATIVE, "native codec failed to build/load"


def test_library_is_keyed_on_source_and_flags(tmp_path, monkeypatch):
    """A library is reused only for the exact source and flags it was built
    from: the build directory travels with a copied checkout, and a stale
    library there (whatever its file time) must never be picked up."""
    import ctypes
    import os

    from tensorflowonspark_tpu.native import build

    monkeypatch.setattr(build, "_CACHE_DIR", str(tmp_path / "cache"))
    os.makedirs(build._CACHE_DIR)
    src = tmp_path / "answer.cc"
    src.write_text('extern "C" int answer() { return 1; }\n')
    # a library built from OTHER source, newer than the source file, under
    # the name the old file-time scheme would have reused
    stale = tmp_path / "cache" / "libanswer.so"
    stale.write_bytes(b"not a library")
    first = build.build_native_lib(str(src), "libanswer.so")
    assert first != str(stale) and ctypes.CDLL(first).answer() == 1
    assert build.build_native_lib(str(src), "libanswer.so") == first  # reused
    src.write_text('extern "C" int answer() { return 2; }\n')
    os.utime(src, (0, 0))  # older than every library: file time says "reuse"
    second = build.build_native_lib(str(src), "libanswer.so")
    assert second != first and ctypes.CDLL(second).answer() == 2
    assert build.build_native_lib(str(src), "libanswer.so",
                                  ("-DX=1",)) not in (first, second)


def test_crc_agreement():
    nb = native()
    for data in [b"", b"a", b"123456789", bytes(range(256)) * 37, b"\x00" * 4096]:
        assert nb.crc32c(data) == tfrecord._crc32c_py(data), data[:16]


def test_frame_agreement():
    nb = native()
    for data in [b"", b"x", b"hello world" * 100]:
        length = len(data).to_bytes(8, "little")
        py = (length
              + tfrecord.masked_crc32c(length).to_bytes(4, "little")
              + data
              + tfrecord.masked_crc32c(data).to_bytes(4, "little"))
        assert nb.frame_record(data) == py


def test_scan_roundtrip_and_corruption():
    nb = native()
    records = [b"a" * i for i in range(0, 300, 7)]
    blob = b"".join(nb.frame_record(r) for r in records)
    spans, consumed = nb.scan_records(blob)
    assert consumed == len(blob)
    assert [blob[o : o + n] for o, n in spans] == records

    bad = bytearray(blob)
    bad[len(nb.frame_record(records[0])) + 13] ^= 0xFF  # corrupt record 1 data
    with pytest.raises(ValueError, match="corrupt"):
        nb.scan_records(bytes(bad))

    spans, consumed = nb.scan_records(blob[:-2])  # truncated tail
    assert len(spans) == len(records) - 1
    assert consumed < len(blob)


def test_file_roundtrip_native_vs_python(tmp_path):
    path = str(tmp_path / "x.tfrecord")
    records = [b"r%d" % i * (i % 50) for i in range(500)]
    tfrecord.write_records(path, records)
    assert list(tfrecord.read_records(path)) == records
