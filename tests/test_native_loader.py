"""Native (C++) tpurecord reader vs the pure-Python reference reader:
byte-identical payloads, same corruption detection, batch reads, and the
dataset integration path."""

import os
import shutil
from pathlib import Path

import numpy as np
import pytest

from tpucfn.data import RecordShardWriter, ShardedDataset, synthetic_cifar10, write_dataset_shards
from tpucfn.data import native
from tpucfn.data.records import read_record_shard

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="native reader not built (no g++?)"
)


def _shard(tmp_path, payloads):
    p = tmp_path / "s.tpurec"
    with RecordShardWriter(p) as w:
        for b in payloads:
            w.write(b)
    return p


def test_native_matches_python_reader(tmp_path):
    payloads = [b"a", b"bb" * 500, b"", b"xyz" * 33]
    p = _shard(tmp_path, payloads)
    assert list(native.read_record_shard_native(p)) == payloads
    assert list(read_record_shard(p)) == payloads


def test_native_random_access_and_batch(tmp_path):
    payloads = [bytes([i]) * (i + 1) for i in range(20)]
    p = _shard(tmp_path, payloads)
    r = native.NativeShardReader(p)
    assert len(r) == 20
    assert r.read(7) == payloads[7]
    assert r.read_batch([3, 1, 19]) == [payloads[3], payloads[1], payloads[19]]
    assert r.read_batch([]) == []
    r.close()


def test_native_crc_detection(tmp_path):
    p = _shard(tmp_path, [b"payload-payload"])
    raw = bytearray(p.read_bytes())
    raw[-3] ^= 0xFF
    p.write_bytes(bytes(raw))
    r = native.NativeShardReader(p)
    with pytest.raises(ValueError, match="CRC"):
        r.read(0)


def test_native_truncation_detection(tmp_path):
    p = _shard(tmp_path, [b"x" * 100] * 10)
    p.write_bytes(p.read_bytes()[:-50])
    with pytest.raises(ValueError, match="truncated"):
        native.NativeShardReader(p)


def test_native_bad_magic(tmp_path):
    p = tmp_path / "junk.tpurec"
    p.write_bytes(b"\x00" * 64)
    with pytest.raises(ValueError, match="magic"):
        native.NativeShardReader(p)


def test_native_out_of_range(tmp_path):
    p = _shard(tmp_path, [b"one"])
    r = native.NativeShardReader(p)
    with pytest.raises(IndexError):
        r.read(5)


def test_dataset_uses_native_reader(tmp_path, monkeypatch):
    paths = write_dataset_shards(synthetic_cifar10(32), tmp_path, num_shards=2)
    calls = []
    orig = native.read_record_shard_native

    def spy(path):
        calls.append(path)
        return orig(path)

    monkeypatch.setattr(native, "read_record_shard_native", spy)
    ds = ShardedDataset(paths, batch_size_per_process=8)
    batches = list(ds.epoch(0))
    assert len(batches) == 4
    assert len(calls) == 2  # both shards went through the native reader


def test_native_and_python_agree_on_dataset(tmp_path):
    paths = write_dataset_shards(synthetic_cifar10(16), tmp_path, num_shards=1)
    a = list(native.read_record_shard_native(paths[0]))
    b = list(read_record_shard(paths[0]))
    assert a == b
    assert len(a) == 16
    from tpucfn.data.records import decode_example

    ex = decode_example(a[0])
    assert ex["image"].shape == (32, 32, 3)
    np.testing.assert_array_equal(ex["image"], decode_example(b[0])["image"])


# ---- the library is built from what git holds (ISSUE 22) -----------------

NATIVE_SRC = Path(native.__file__).resolve().parent.parent.parent / "native"


@pytest.fixture
def native_dir(tmp_path, monkeypatch):
    """A private copy of native/ wired into a fresh (unloaded) loader."""
    d = tmp_path / "native"
    d.mkdir()
    for name in ("tpurecord.cc", "build.sh"):
        shutil.copy(NATIVE_SRC / name, d / name)
    monkeypatch.setattr(native, "_NATIVE_DIR", d)
    monkeypatch.setattr(native, "_LIB_PATH", d / "libtpurecord.so")
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_lib_error", None)
    return d


def test_library_older_than_its_source_is_rebuilt(native_dir):
    lib = native_dir / "libtpurecord.so"
    lib.write_bytes(b"a stale artefact that is not even a library")
    src_mtime = (native_dir / "tpurecord.cc").stat().st_mtime
    os.utime(lib, (src_mtime - 60, src_mtime - 60))
    assert native._lib_is_stale()
    assert native.native_available(), native._lib_error
    assert native._lib_error is None
    assert lib.read_bytes()[:4] == b"\x7fELF"
    assert not native._lib_is_stale()


def test_failed_build_sets_lib_error_and_falls_back(native_dir):
    (native_dir / "build.sh").write_text(
        "echo 'no toolchain on this host' >&2\nexit 1\n")
    assert not native.native_available()
    assert "no toolchain on this host" in native._lib_error
    with pytest.raises(RuntimeError, match="native reader unavailable"):
        native.NativeShardReader(native_dir / "missing.tpurec")
