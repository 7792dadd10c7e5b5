import numpy as np
import pytest

from tpucfn.data import (
    RecordShardWriter,
    ShardedDataset,
    prefetch_to_mesh,
    read_record_shard,
    synthetic_cifar10,
    write_dataset_shards,
)
from tpucfn.data.records import decode_example


def test_record_roundtrip(tmp_path):
    p = tmp_path / "a.tpurec"
    with RecordShardWriter(p) as w:
        w.write(b"hello")
        w.write(b"world" * 100)
    assert list(read_record_shard(p)) == [b"hello", b"world" * 100]


def test_record_crc_detects_corruption(tmp_path):
    p = tmp_path / "a.tpurec"
    with RecordShardWriter(p) as w:
        w.write(b"payload-payload")
    raw = bytearray(p.read_bytes())
    raw[-3] ^= 0xFF  # flip a payload byte
    p.write_bytes(bytes(raw))
    with pytest.raises(ValueError, match="CRC"):
        list(read_record_shard(p))


def test_record_truncation_detected(tmp_path):
    p = tmp_path / "a.tpurec"
    with RecordShardWriter(p) as w:
        for i in range(10):
            w.write(b"x" * 100)
    p.write_bytes(p.read_bytes()[:-50])
    with pytest.raises(ValueError):
        list(read_record_shard(p))


def test_bad_magic(tmp_path):
    p = tmp_path / "junk.tpurec"
    p.write_bytes(b"\x00" * 64)
    with pytest.raises(ValueError, match="magic"):
        list(read_record_shard(p))


def test_write_dataset_shards_roundtrip(tmp_path):
    paths = write_dataset_shards(synthetic_cifar10(32), tmp_path, num_shards=4)
    assert len(paths) == 4
    examples = [decode_example(b) for p in paths for b in read_record_shard(p)]
    assert len(examples) == 32
    assert examples[0]["image"].shape == (32, 32, 3)
    assert examples[0]["label"].shape == ()


def test_sharded_dataset_process_ownership(tmp_path):
    paths = write_dataset_shards(synthetic_cifar10(64), tmp_path, num_shards=4)
    d0 = ShardedDataset(paths, batch_size_per_process=8, process_index=0, process_count=2)
    d1 = ShardedDataset(paths, batch_size_per_process=8, process_index=1, process_count=2)
    assert set(d0.local_shards) | set(d1.local_shards) == {str(p) for p in paths}
    assert not set(d0.local_shards) & set(d1.local_shards)


def test_more_processes_than_shards_raises(tmp_path):
    paths = write_dataset_shards(synthetic_cifar10(8), tmp_path, num_shards=2)
    with pytest.raises(ValueError, match="owns no shards"):
        ShardedDataset(paths, batch_size_per_process=2, process_index=2, process_count=4)


def test_epoch_determinism_and_reshuffle(tmp_path):
    paths = write_dataset_shards(synthetic_cifar10(64), tmp_path, num_shards=2)
    ds = ShardedDataset(paths, batch_size_per_process=16, seed=7)
    e0a = [b["label"] for b in ds.epoch(0)]
    e0b = [b["label"] for b in ds.epoch(0)]
    e1 = [b["label"] for b in ds.epoch(1)]
    np.testing.assert_array_equal(np.concatenate(e0a), np.concatenate(e0b))
    assert not np.array_equal(np.concatenate(e0a), np.concatenate(e1))


def test_batch_shapes_and_len(tmp_path):
    paths = write_dataset_shards(synthetic_cifar10(70), tmp_path, num_shards=2)
    ds = ShardedDataset(paths, batch_size_per_process=16)
    assert len(ds) == 4  # 70 // 16, drop remainder
    batches = list(ds.epoch(0))
    assert len(batches) == 4
    assert batches[0]["image"].shape == (16, 32, 32, 3)


def test_prefetch_to_mesh_yields_sharded(tmp_path, mesh_dp8):
    from jax.sharding import PartitionSpec as P

    paths = write_dataset_shards(synthetic_cifar10(64), tmp_path, num_shards=2)
    ds = ShardedDataset(paths, batch_size_per_process=16)
    out = list(prefetch_to_mesh(ds.epoch(0), mesh_dp8))
    assert len(out) == 4
    assert out[0]["image"].sharding.spec == P(("data", "fsdp", "expert"))
    assert out[0]["image"].addressable_shards[0].data.shape[0] == 2


def test_prefetch_propagates_errors(mesh_dp8):
    def bad_iter():
        yield {"x": np.ones((8, 2), np.float32)}
        raise RuntimeError("decode exploded")

    it = prefetch_to_mesh(bad_iter(), mesh_dp8)
    next(it)
    with pytest.raises(RuntimeError, match="decode exploded"):
        list(it)


def test_sharded_dataset_num_workers_parallel_decode(tmp_path):
    """num_workers>0 runs the transform in a thread pool: batches are
    identical across worker counts (per-example seeds are drawn
    sequentially; map preserves order) and reproducible run-to-run."""
    import numpy as np

    from tpucfn.data import write_dataset_shards
    from tpucfn.data.pipeline import ShardedDataset

    rs = np.random.RandomState(0)
    examples = [{"x": rs.randn(4).astype(np.float32),
                 "label": np.int32(i % 3)} for i in range(64)]
    shards = write_dataset_shards(iter(examples), tmp_path, num_shards=4)

    def noisy(ex, aug_rs):
        return {"x": ex["x"] + aug_rs.randn(4).astype(np.float32),
                "label": ex["label"]}

    def batches(workers):
        ds = ShardedDataset(shards, batch_size_per_process=16, seed=7,
                            process_index=0, process_count=1,
                            transform=noisy, num_workers=workers)
        return list(ds.epoch(0))

    b4 = batches(4)
    b1 = batches(1)
    b4_again = batches(4)
    assert len(b4) == 4
    for a, b, c in zip(b4, b1, b4_again):
        np.testing.assert_array_equal(a["x"], b["x"])
        np.testing.assert_array_equal(a["x"], c["x"])
        np.testing.assert_array_equal(a["label"], b["label"])


def _mp_shards(tmp_path, n=48, num_shards=6):
    import numpy as np

    from tpucfn.data import write_dataset_shards

    rs = np.random.RandomState(0)
    examples = [{"x": rs.randn(3).astype(np.float32),
                 "uid": np.int32(i)} for i in range(n)]
    return write_dataset_shards(iter(examples), tmp_path, num_shards=num_shards)


def test_multiprocess_loader_one_worker_matches_sharded_dataset(tmp_path):
    import numpy as np

    from tpucfn.data.pipeline import MultiProcessLoader, ShardedDataset
    from tpucfn.data.transforms import normalize

    shards = _mp_shards(tmp_path)
    kw = dict(batch_size_per_process=8, seed=3,
              transform=normalize((0.5,), (2.0,), key="x"))
    ds = ShardedDataset(shards, process_index=0, process_count=1, **kw)
    ref = list(ds.batches(2))
    with MultiProcessLoader(shards, num_workers=1, process_index=0,
                            process_count=1, **kw) as loader:
        got = list(loader.batches(2))
    assert len(got) == len(ref)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a["x"], b["x"])
        np.testing.assert_array_equal(a["uid"], b["uid"])


def test_multiprocess_loader_deterministic_and_covers_epoch(tmp_path):
    import numpy as np

    from tpucfn.data.pipeline import MultiProcessLoader

    shards = _mp_shards(tmp_path)

    def run():
        with MultiProcessLoader(shards, num_workers=3, process_index=0,
                                process_count=1, batch_size_per_process=4,
                                seed=1) as loader:
            return list(loader.batches(1))

    a, b = run(), run()
    assert len(a) == 12  # 48 examples / batch 4, all workers drained
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["uid"], y["uid"])
    seen = sorted(int(u) for batch in a for u in batch["uid"])
    assert seen == list(range(48))  # every example exactly once per epoch


def test_multiprocess_loader_propagates_worker_errors(tmp_path):
    import pytest

    from tpucfn.data.pipeline import MultiProcessLoader
    from tpucfn.data.transforms import RandomCrop

    shards = _mp_shards(tmp_path)
    # RandomCrop on a rank-1 "x" raises inside the worker
    loader = MultiProcessLoader(shards, num_workers=2, process_index=0,
                                process_count=1, batch_size_per_process=4,
                                transform=RandomCrop(2, key="x"))
    with pytest.raises(RuntimeError, match="loader worker"):
        list(loader.batches(1))


def test_multiprocess_loader_requires_enough_shards(tmp_path):
    import pytest

    from tpucfn.data.pipeline import MultiProcessLoader

    shards = _mp_shards(tmp_path, num_shards=2)
    with pytest.raises(ValueError, match="num_workers"):
        MultiProcessLoader(shards, num_workers=4, process_index=0,
                           process_count=1, batch_size_per_process=4)


def test_multiprocess_loader_len_matches_stream(tmp_path):
    # ADVICE r3 (medium): epoch-driven loops compute
    # len(ds) * num_epochs; MultiProcessLoader must agree with what its
    # stream actually yields.
    from tpucfn.data.pipeline import MultiProcessLoader

    shards = _mp_shards(tmp_path)  # 48 examples over 6 shards
    with MultiProcessLoader(shards, num_workers=3, process_index=0,
                            process_count=1, batch_size_per_process=4,
                            seed=1) as loader:
        n = len(loader)
        got = list(loader.batches(1))
    assert n == len(got) == 12
    # Remainder rounding is per-worker: 5 shards / 2 workers with an
    # odd split still matches the stream.
    shards5 = _mp_shards(tmp_path / "odd", n=44, num_shards=5)
    with MultiProcessLoader(shards5, num_workers=2, process_index=0,
                            process_count=1, batch_size_per_process=8,
                            seed=1) as loader:
        assert len(loader) == len(list(loader.batches(1)))


def test_multiprocess_loader_detects_killed_worker(tmp_path):
    # ADVICE r3: a worker killed without posting (OOM SIGKILL) must
    # surface as an error, not hang the parent on Queue.get forever.
    import pytest

    from tpucfn.data.pipeline import MultiProcessLoader

    shards = _mp_shards(tmp_path)
    loader = MultiProcessLoader(shards, num_workers=2, process_index=0,
                                process_count=1, batch_size_per_process=4,
                                prefetch=1)
    it = loader.batches(None)
    next(it)  # workers are up and producing
    for p in loader._procs:
        p.kill()  # simulate the OOM killer: no "error" message posted
    with pytest.raises(RuntimeError, match="died"):
        # Drain: queues may hold a few already-produced batches; the
        # dead-worker check fires once they empty. _get polls fast.
        while True:
            loader._get(0, timeout_s=0.2)
            loader._get(1, timeout_s=0.2)


# -- MultiProcessLoader shutdown / torn-queue edges (ISSUE 11 satellite) ----
# The disaggregated input service reuses these exact paths per trainer
# stream, so they are pinned here rather than rediscovered over a socket.


class _SlowTransform:
    """Module-level so spawn can pickle it by reference."""

    def __init__(self, seconds):
        self.seconds = seconds

    def __call__(self, ex, rs):
        import time

        time.sleep(self.seconds)
        return ex


@pytest.mark.slow
def test_multiprocess_loader_worker_death_surfaces_via_batches(tmp_path):
    """The public batches() path (not just _get) must raise the clean
    dead-worker error when a worker is killed mid-batch without posting
    — the stream must never hang the consumer."""
    import pytest

    from tpucfn.data.pipeline import MultiProcessLoader

    shards = _mp_shards(tmp_path, n=96, num_shards=6)
    loader = MultiProcessLoader(shards, num_workers=2, process_index=0,
                                process_count=1, batch_size_per_process=4,
                                prefetch=1,
                                transform=_SlowTransform(0.02))
    it = loader.batches(None)
    next(it)  # workers up and producing
    for p in loader._procs:
        p.kill()  # OOM-killer shape: no "error" message posted
    with pytest.raises(RuntimeError, match="died"):
        for _ in range(10_000):
            next(it)


@pytest.mark.slow
def test_multiprocess_loader_close_during_iteration(tmp_path):
    """close() from another thread mid-iteration (the input service's
    stream teardown) ends the iteration with a clean RuntimeError, not
    an IndexError on the torn queue list — and close is idempotent."""
    import threading

    import pytest

    from tpucfn.data.pipeline import MultiProcessLoader

    shards = _mp_shards(tmp_path, n=96, num_shards=6)
    loader = MultiProcessLoader(shards, num_workers=2, process_index=0,
                                process_count=1, batch_size_per_process=4,
                                prefetch=1,
                                transform=_SlowTransform(0.01))
    it = loader.batches(None)
    next(it)
    t = threading.Thread(target=loader.close)
    t.start()
    with pytest.raises(RuntimeError, match="closed|died"):
        for _ in range(10_000):
            next(it)
    t.join(timeout=10)
    assert not t.is_alive()
    loader.close()  # double close is a no-op
    assert loader._procs == [] and loader._queues == []


@pytest.mark.slow
def test_multiprocess_loader_get_timeout_polls_until_batch(tmp_path):
    """_get with a timeout shorter than the batch build time polls
    through queue.Empty cycles while the worker is ALIVE and returns
    the batch — a slow worker is slow, not dead."""
    from tpucfn.data.pipeline import MultiProcessLoader

    shards = _mp_shards(tmp_path, n=16, num_shards=2)
    loader = MultiProcessLoader(shards, num_workers=2, process_index=0,
                                process_count=1, batch_size_per_process=8,
                                prefetch=1,
                                transform=_SlowTransform(0.05))
    try:
        loader._start(1)
        tag, payload = loader._get(0, timeout_s=0.05)
        assert tag == "batch"
        assert payload["uid"].shape == (8,)
    finally:
        loader.close()


@pytest.mark.slow
def test_multiprocess_loader_get_after_close_raises_cleanly(tmp_path):
    import pytest

    from tpucfn.data.pipeline import MultiProcessLoader

    shards = _mp_shards(tmp_path)
    loader = MultiProcessLoader(shards, num_workers=2, process_index=0,
                                process_count=1, batch_size_per_process=4)
    loader._start(1)
    loader.close()
    with pytest.raises(RuntimeError, match="closed"):
        loader._get(0, timeout_s=0.05)


# ---- a large batch is assembled in pieces (ISSUE 26) ------------------------
# Every path of ShardedDataset must yield, split, the bytes it yields whole.
# The tests' rows are small, so the split is forced by taking the threshold
# away and giving the process `cores` cores; the reference is the same dataset
# under the module's own constants (one np.stack, today's statement).

def _rows(n=50, ragged_at=None):
    rs = np.random.RandomState(3)
    return [{"image": rs.rand(6, 5, 3).astype(np.float32),
             "mask": rs.rand(4 if i != ragged_at else 5) > 0.5,
             "half": rs.rand(3).astype(np.float16),
             "label": np.int64(i)} for i in range(n)]  # the label is 0-d


def _force_split(monkeypatch, cores=3):
    from tpucfn.data import pipeline

    monkeypatch.setattr(pipeline, "_ASSEMBLE_PIECE_BYTES", 1)
    monkeypatch.setattr(pipeline.os, "sched_getaffinity",
                        lambda pid: set(range(cores)))


def _assemble_threads():
    import threading

    return [t for t in threading.enumerate()
            if t.name.startswith("tpucfn-assemble-")]


def _jitter(ex, aug_rs):
    return dict(ex, image=ex["image"] + aug_rs.rand(6, 5, 3).astype(np.float32))


@pytest.mark.parametrize("case, kwargs", [
    ("not_divisible", dict(batch_size_per_process=10)),  # 10 rows, 3 pieces
    ("remainder", dict(batch_size_per_process=16, drop_remainder=False)),
    ("unshuffled", dict(batch_size_per_process=7, shuffle=False)),
    ("transform", dict(batch_size_per_process=10, transform=_jitter)),
    ("transform_workers", dict(batch_size_per_process=10, transform=_jitter,
                               num_workers=2)),
    ("streaming", dict(batch_size_per_process=10, cache_in_memory=False,
                       shuffle_buffer=8)),
    ("streaming_remainder", dict(batch_size_per_process=16,
                                 cache_in_memory=False, drop_remainder=False)),
])
def test_split_assembly_is_byte_identical_to_whole(tmp_path, monkeypatch,
                                                   case, kwargs):
    from tpucfn.data import pipeline

    shards = write_dataset_shards(iter(_rows()), tmp_path, num_shards=2)

    def epochs():
        ds = ShardedDataset(shards, seed=11, process_index=0, process_count=1,
                            **kwargs)
        out = []
        for batch in ds.batches(2):
            out.append((batch, pipeline._take_pieces()))
        return out

    whole = epochs()
    _force_split(monkeypatch, cores=3)
    split = epochs()
    assert len(split) == len(whole) == 2 * (
        -(-50 // kwargs["batch_size_per_process"])
        if kwargs.get("drop_remainder") is False
        else 50 // kwargs["batch_size_per_process"])
    assert {p for _, p in whole} == {1}
    # a remainder of 2 rows cannot make 3 pieces
    assert {p for _, p in split} <= {2, 3} and 3 in {p for _, p in split}
    for (w, _), (s, _) in zip(whole, split):
        assert list(w) == list(s) == ["image", "mask", "half", "label"]
        for k in w:
            assert s[k].dtype == w[k].dtype and s[k].shape == w[k].shape
            assert s[k].tobytes() == w[k].tobytes(), (case, k)
    assert split[0][0]["label"].shape == (kwargs["batch_size_per_process"],)


def test_a_held_batch_is_never_written_again(tmp_path, monkeypatch):
    """Depth 2 plus three more batches later the first is what it was, and no
    two batches share memory: every output is a fresh array."""
    shards = write_dataset_shards(iter(_rows(96)), tmp_path, num_shards=2)
    _force_split(monkeypatch)
    ds = ShardedDataset(shards, batch_size_per_process=8, seed=5,
                        process_index=0, process_count=1)
    it = ds.batches(1)
    held = next(it)
    before = {k: v.tobytes() for k, v in held.items()}
    later = [next(it) for _ in range(2 + 3)]
    assert {k: v.tobytes() for k, v in held.items()} == before
    everything = [held] + later
    for i, a in enumerate(everything):
        for b in everything[i + 1:]:
            assert not any(np.shares_memory(a[k], b[k]) for k in a)


def test_small_batches_take_the_whole_path_and_make_no_pool(tmp_path,
                                                            monkeypatch):
    """The size rule under the module's own constants: the decoder cells'
    32 KB of tokens, CIFAR's rows and these tests' stay whole and the pool
    is never made; `rn50-cached`'s 154 MB splits, by the cores there are."""
    from tpucfn.data import pipeline

    monkeypatch.setattr(pipeline._AssemblePool, "_shared", None)
    monkeypatch.setattr(pipeline.os, "sched_getaffinity",
                        lambda pid: set(range(13)))
    paths = write_dataset_shards(synthetic_cifar10(64), tmp_path, num_shards=2)
    ds = ShardedDataset(paths, batch_size_per_process=32)
    assert len(list(ds.epoch(0))) == 2
    assert pipeline._take_pieces() == 1
    assert pipeline._AssemblePool._shared is None
    assert pipeline._pieces_for(1 * 8192 * 4, 1) == 1  # mistral7b-s8192
    assert pipeline._pieces_for(8 * 1024 * 4, 8) == 1  # mistral7b-s1024
    assert pipeline._pieces_for(256 * 32 * 32 * 3 * 4, 256) == 1  # CIFAR b256
    rn50 = 256 * 224 * 224 * 3 * 4
    assert pipeline._pieces_for(rn50, 256) == pipeline._ASSEMBLE_MAX_PIECES
    monkeypatch.setattr(pipeline.os, "sched_getaffinity", lambda pid: {0, 1, 2})
    assert pipeline._pieces_for(rn50, 256) == 3
    monkeypatch.setattr(pipeline.os, "sched_getaffinity", lambda pid: {0})
    assert pipeline._pieces_for(rn50, 256) == 1
    # a MultiProcessLoader's workers share the host's cores
    monkeypatch.setattr(pipeline.os, "sched_getaffinity",
                        lambda pid: set(range(8)))
    monkeypatch.setattr(pipeline, "_core_sharers", 4)
    assert pipeline._pieces_for(rn50, 256) == 2
    assert pipeline._AssemblePool._shared is None


@pytest.mark.parametrize("fault", ["ragged", "a_piece_fails"])
def test_a_failed_assembly_raises_from_next_and_does_not_hang(
        tmp_path, monkeypatch, fault):
    import threading

    from tpucfn.data import pipeline

    rows = _rows(40, ragged_at=13 if fault == "ragged" else None)
    shards = write_dataset_shards(iter(rows), tmp_path, num_shards=2)
    _force_split(monkeypatch)
    if fault == "a_piece_fails":
        real = np.stack

        def stack(arrays, *a, **kw):
            if threading.current_thread().name.startswith("tpucfn-assemble-"):
                raise MemoryError("no room for this piece")
            return real(arrays, *a, **kw)

        monkeypatch.setattr(pipeline.np, "stack", stack)
    ds = ShardedDataset(shards, batch_size_per_process=40, shuffle=False,
                        process_index=0, process_count=1)
    raised = []

    def pull():
        try:
            next(ds.batches(1))
        except Exception as e:  # noqa: BLE001 — the test looks at it
            raised.append(e)

    t = threading.Thread(target=pull)
    t.start()
    t.join(timeout=60)
    assert not t.is_alive()
    (e,) = raised
    if fault == "ragged":  # np.stack's own complaint, as before the split
        assert isinstance(e, ValueError) and "same shape" in str(e)
    else:
        assert isinstance(e, MemoryError)


def test_two_datasets_share_one_pool(tmp_path, monkeypatch):
    from tpucfn.data import pipeline

    shards = write_dataset_shards(iter(_rows()), tmp_path, num_shards=2)
    _force_split(monkeypatch)
    kw = dict(batch_size_per_process=10, process_index=0, process_count=1)
    list(ShardedDataset(shards, seed=1, **kw).epoch(0))
    pool, threads = pipeline._AssemblePool._shared, _assemble_threads()
    assert pool is not None
    assert len(threads) >= pipeline._ASSEMBLE_MAX_PIECES - 1
    assert all(t.daemon for t in threads)
    list(ShardedDataset(shards, seed=2, **kw).epoch(0))
    assert pipeline._AssemblePool._shared is pool
    assert _assemble_threads() == threads


def test_loaders_on_many_threads_share_the_pool_without_mixing_rows(
        tmp_path, monkeypatch):
    """More loader threads than cores over the one pool, the interpreter
    switching threads every 10 us: every batch is still its own."""
    import sys
    import threading

    shards = write_dataset_shards(iter(_rows(64)), tmp_path, num_shards=2)
    kw = dict(batch_size_per_process=16, process_index=0, process_count=1)
    want = {seed: [b["image"].tobytes() + b["label"].tobytes()
                   for b in ShardedDataset(shards, seed=seed, **kw).batches(3)]
            for seed in range(12)}
    _force_split(monkeypatch, cores=8)
    got, errors = {}, []

    def load(seed):
        try:
            got[seed] = [b["image"].tobytes() + b["label"].tobytes() for b in
                         ShardedDataset(shards, seed=seed, **kw).batches(3)]
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=load, args=(s,)) for s in want]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errors
    assert got == want


@pytest.mark.parametrize("stream, pieces", [
    ("split", 3), ("whole", 1), ("not_a_local_dataset", None)])
def test_prefetch_input_load_rows_carry_pieces(tmp_path, monkeypatch,
                                               mesh_dp8, stream, pieces):
    from tpucfn.obs.trace import Tracer, read_trace_file

    shards = write_dataset_shards(iter(_rows(48)), tmp_path / "d", num_shards=2)
    ds = ShardedDataset(shards, batch_size_per_process=16, process_index=0,
                        process_count=1)
    if stream == "split":
        _force_split(monkeypatch, cores=3)
    it = ds.batches(1)
    if stream == "not_a_local_dataset":  # as over the input plane
        it = iter([dict(b) for b in list(it)])
    tracer = Tracer(tmp_path / "t.jsonl")
    assert len(list(prefetch_to_mesh(it, mesh_dp8, tracer=tracer))) == 3
    tracer.close()
    rows = [r for r in read_trace_file(tmp_path / "t.jsonl")
            if r.get("kind") == "span"]
    loads = [r for r in rows if r["name"] == "input_load"]
    assert len(loads) == 4 and {r["tid"] for r in loads} == {"tpucfn-prefetch"}
    assert loads[-1]["attrs"] == {"end_of_stream": True}
    assert [r["attrs"].get("pieces") for r in loads[:-1]] == [pieces] * 3
    assert all("pieces" not in r["attrs"]
               for r in rows if r["name"] == "input_place")
