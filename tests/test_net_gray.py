"""Fast per-plane gray-failure tests (ISSUE 15): trickle / stall /
partition injected by a real ChaosProxy in front of a real InputService
or ArtifactServer socket — the client-side deadlines must notice within
their bound and degrade (failover → local) with the sequence unchanged.
Everything is numpy + localhost sockets, seconds per test; the slow
launch-fan-out drills live in test_net_gray_e2e.py."""

import itertools
import time

import numpy as np
import pytest

from tpucfn.compilecache.service import (
    ArtifactClient,
    ArtifactServer,
    CompileCacheClient,
)
from tpucfn.compilecache.store import ArtifactStore, cache_key
from tpucfn.data import write_dataset_shards
from tpucfn.data.pipeline import ShardedDataset
from tpucfn.data.service import (
    InputService,
    ResilientBatchStream,
    ServiceBatchStream,
    ServiceError,
)
from tpucfn.net.proxy import ChaosProxy
from tpucfn.obs.registry import MetricRegistry


def _shards(tmp_path, n=48, num_shards=6, dim=64):
    rs = np.random.RandomState(0)
    examples = [{"x": rs.randn(dim).astype(np.float32),
                 "uid": np.int32(i)} for i in range(n)]
    return write_dataset_shards(iter(examples), tmp_path,
                                num_shards=num_shards)


def _local(shards, trainer=0, pc=1, batch=4, seed=3, **kw):
    return ShardedDataset(shards, batch_size_per_process=batch, seed=seed,
                          process_index=trainer, process_count=pc, **kw)


# A fault here lasts FAULT_S; the client's deadline is 1 s.  "Noticed by the
# deadline, not by the fault's end" is a bound far from both: a tier-1 worker
# on a shared CPU reads 1 s of deadline as anything up to several seconds, so
# a bound near the deadline tests the machine and not the client.
FAULT_S = 120.0
NOTICED_WITHIN_S = FAULT_S / 4


# The whole epoch is 15 KB in twelve frames: once the first batch is read the
# rest may already lie in socket buffers, out of reach of a fault injected
# then, and the stream ends without degrading (seen under six workers).  So
# the proxy holds the down direction after a few frames, the fault is injected
# after the first batch as before, and the hold is let go: the fault lands
# mid-stream whatever the scheduler does.
HOLD_AFTER_BYTES = 4096


def _hold_after_head(proxy):
    proxy.inject("stall", direction="down", after_bytes=HOLD_AFTER_BYTES)


def _let_go(proxy):
    proxy.inject("stall", direction="down", duration_s=1e-3)


def _assert_streams_equal(got, ref):
    assert len(got) == len(ref)
    for a, b in zip(got, ref):
        assert sorted(a) == sorted(b)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


@pytest.fixture
def plane(tmp_path):
    """A real InputService with a ChaosProxy in front of it."""
    shards = _shards(tmp_path)
    svc = InputService(shards, num_trainers=1, batch_size_per_process=4,
                       seed=3, host="127.0.0.1",
                       send_deadline_s=5.0).start()
    proxy = ChaosProxy(svc.address).start()
    yield shards, svc, proxy
    proxy.close()
    svc.close()


def _resilient(shards, proxy, *, registry=None, op_deadline_s=1.0):
    ds = _local(shards)
    return ResilientBatchStream(
        [proxy.address], 0,
        local_factory=lambda skip: itertools.islice(
            _local(shards).batches(1), skip, None),
        process_count=1, batch_size=4, seed=3, num_epochs=1,
        connect_retry_s=0.5, op_deadline_s=op_deadline_s,
        registry=registry), ds


def test_input_trickle_degrades_within_the_deadline(plane):
    """The headline gray failure: mid-stream the input plane starts
    TRICKLING (bytes keep flowing, so per-chunk timeouts never fire) —
    the end-to-end frame deadline must notice within its bound and the
    stream degrade to local at the exact cursor, bit-identical."""
    shards, svc, proxy = plane
    registry = MetricRegistry()
    _hold_after_head(proxy)
    stream, ds = _resilient(shards, proxy, registry=registry)
    ref = list(_local(shards).batches(1))
    got = [next(stream)]  # healthy first batch through the proxy
    proxy.inject("throttle", rate_bps=64.0, duration_s=FAULT_S)
    _let_go(proxy)
    t0 = time.monotonic()
    got.extend(stream)
    detect = time.monotonic() - t0
    # what the deadline guarantees: the stream went local at the exact
    # cursor, because a deadline fired and not because the fault ended
    assert stream.degraded
    assert stream.cursor == len(ref)
    _assert_streams_equal(got, ref)
    v = registry.varz()["metrics"]
    assert v["net_input_deadline_exceeded_total"] >= 1
    # never the multi-minute per-chunk worst case the deadline retires
    assert detect < NOTICED_WITHIN_S, f"degradation took {detect:.1f}s"


def test_input_stall_degrades_within_the_deadline(plane):
    shards, svc, proxy = plane
    _hold_after_head(proxy)
    stream, ds = _resilient(shards, proxy)
    ref = list(_local(shards).batches(1))
    got = [next(stream)]
    proxy.inject("stall", duration_s=FAULT_S)  # takes the hold's place
    t0 = time.monotonic()
    got.extend(stream)
    assert time.monotonic() - t0 < NOTICED_WITHIN_S
    assert stream.degraded
    assert stream.cursor == len(ref)
    _assert_streams_equal(got, ref)


def test_input_partition_down_degrades_within_the_deadline(plane):
    """One-way partition: the trainer's requests reach the host, the
    host's bytes never arrive — asymmetric reachability, the half-open
    class."""
    shards, svc, proxy = plane
    _hold_after_head(proxy)
    stream, ds = _resilient(shards, proxy)
    ref = list(_local(shards).batches(1))
    got = [next(stream)]
    proxy.inject("partition", direction="down", duration_s=FAULT_S)
    _let_go(proxy)
    t0 = time.monotonic()
    got.extend(stream)
    assert time.monotonic() - t0 < NOTICED_WITHIN_S
    assert stream.degraded
    assert stream.cursor == len(ref)
    _assert_streams_equal(got, ref)


def test_input_torn_frame_degrades_bit_identical(plane):
    shards, svc, proxy = plane
    ref = list(_local(shards).batches(1))
    # Armed BEFORE the stream connects, at a fixed offset into the
    # ~14 KB epoch (handshake and the first two ~1.2 KB frames pass,
    # the third tears).  The whole epoch crosses the proxy within
    # milliseconds of the connect, so a tear injected after the first
    # batch raced the producer and, on a loaded host, never fired.
    proxy.inject("tear", after_bytes=3000, direction="down")
    stream, ds = _resilient(shards, proxy)
    got = [next(stream)]  # a healthy batch flowed before the fault
    got.extend(stream)
    assert stream.degraded
    _assert_streams_equal(got, ref)


def test_input_server_drops_stalled_trainer_and_frees_the_stream(tmp_path):
    """Satellite: the server side of the same coin — a trainer that
    connects, reads a little, then blackholes must not pin its producer
    (and queue_batches of encoded batches) for the old 5-minute window;
    the per-frame send deadline drops it and counts the stall."""
    shards = _shards(tmp_path, n=400, num_shards=4, dim=4096)
    registry = MetricRegistry()
    svc = InputService(shards, num_trainers=1, batch_size_per_process=8,
                       seed=3, host="127.0.0.1", queue_batches=2,
                       sndbuf_bytes=32 * 1024,
                       send_deadline_s=0.8, registry=registry).start()
    try:
        stream = ServiceBatchStream(svc.address, 0, process_count=1,
                                    batch_size=8, seed=3, num_epochs=1,
                                    rcvbuf_bytes=32 * 1024)
        next(stream)  # one healthy batch, then the trainer goes silent
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            v = registry.varz()["metrics"]
            if v.get("input_send_stalls_total", 0) >= 1:
                break
            time.sleep(0.05)
        v = registry.varz()["metrics"]
        assert v["input_send_stalls_total"] == 1
        # the stream is torn down like a disconnect: producer released
        deadline = time.monotonic() + 5.0
        while svc._live_streams() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not svc._live_streams()
        stream.close()
    finally:
        svc.close()


# -- compile-artifact plane -------------------------------------------------


def _publish_entry(store_dir, payload_kb=512):
    store = ArtifactStore(store_dir)
    key = cache_key({"program": "gray-drill"})
    payload = bytes(range(256)) * (payload_kb * 4)  # payload_kb KiB
    store.put(key, payload, {"key": key, "label": "gray"})
    return key, payload


def test_artifact_stall_mid_payload_times_out_within_op_deadline(tmp_path):
    """A GET whose multi-hundred-KB payload stalls mid-stream (the
    connection held open) must fail the op inside op_deadline_s — the
    per-chunk shape waited recv_timeout_s per chunk, forever."""
    key, payload = _publish_entry(tmp_path / "store")
    srv = ArtifactServer(tmp_path / "store", host="127.0.0.1").start()
    proxy = ChaosProxy(srv.address).start()
    try:
        # stall the DOWN direction mid-payload: handshake passes, the
        # artifact tears off at 64 KiB and then nothing, forever
        proxy.inject("stall", duration_s=300.0, direction="down",
                     after_bytes=64 * 1024)
        client = ArtifactClient(proxy.address, op_deadline_s=1.0)
        t0 = time.monotonic()
        with pytest.raises(ServiceError, match="deadline"):
            client.get(key)
        assert time.monotonic() - t0 < 5.0
    finally:
        proxy.close()
        srv.close()


def test_stalled_artifact_server_degrades_to_local_compile(tmp_path):
    """The acceptance shape, fast form: get_or_compile against a
    stalled artifact server compiles locally within the op deadline —
    latency cost, never a hang, and the result is the same program."""
    key, payload = _publish_entry(tmp_path / "srvstore")
    srv = ArtifactServer(tmp_path / "srvstore", host="127.0.0.1").start()
    proxy = ChaosProxy(srv.address).start()
    registry = MetricRegistry()
    compiled = []
    try:
        proxy.inject("stall", duration_s=300.0, direction="down",
                     after_bytes=16 * 1024)
        client = CompileCacheClient(
            ArtifactStore(tmp_path / "localstore"), [proxy.address],
            registry=registry, op_deadline_s=1.0, wait_s=2.0)
        t0 = time.monotonic()
        result, outcome = client.get_or_compile(
            key, lambda: compiled.append(1) or b"the-program")
        wall = time.monotonic() - t0
        assert (result, outcome) == (b"the-program", "compile")
        assert compiled == [1]
        assert wall < 10.0, f"degrade-to-compile took {wall:.1f}s"
        v = registry.varz()["metrics"]
        assert v["net_compilecache_deadline_exceeded_total"] >= 1
        assert v["compilecache_fetch_failures_total"] >= 1
    finally:
        proxy.close()
        srv.close()


def test_artifact_rst_degrades_to_local_compile_fast(tmp_path):
    key, payload = _publish_entry(tmp_path / "srvstore", payload_kb=64)
    srv = ArtifactServer(tmp_path / "srvstore", host="127.0.0.1").start()
    proxy = ChaosProxy(srv.address).start()
    try:
        proxy.inject("partition", direction="down", duration_s=300.0)
        client = CompileCacheClient(None, [proxy.address],
                                    op_deadline_s=0.5, wait_s=1.0)
        t0 = time.monotonic()
        result, outcome = client.get_or_compile(key, lambda: b"prog")
        assert (result, outcome) == (b"prog", "compile")
        assert time.monotonic() - t0 < 8.0
    finally:
        proxy.close()
        srv.close()


def test_healthy_proxy_passthrough_fetch_is_bit_identical(tmp_path):
    """Control: through a fault-free proxy the plane behaves exactly as
    without it — the fetch hits and verifies."""
    key, payload = _publish_entry(tmp_path / "srvstore", payload_kb=128)
    srv = ArtifactServer(tmp_path / "srvstore", host="127.0.0.1").start()
    proxy = ChaosProxy(srv.address).start()
    try:
        client = CompileCacheClient(None, [proxy.address], op_deadline_s=5.0)
        result, outcome = client.get_or_compile(
            key, lambda: (_ for _ in ()).throw(AssertionError("no compile")))
        assert outcome == "fetch" and result == payload
    finally:
        proxy.close()
        srv.close()


def test_send_deadline_zero_disables_the_bound(tmp_path):
    """Review fix: 0 means DISABLED (the sibling-knob convention:
    --serve-for 0, duration_s 0) — not an already-expired deadline that
    drops every stream at frame 1."""
    shards = _shards(tmp_path)
    svc = InputService(shards, num_trainers=1, batch_size_per_process=4,
                       seed=3, host="127.0.0.1",
                       send_deadline_s=0.0).start()
    try:
        stream = ServiceBatchStream(svc.address, 0, process_count=1,
                                    batch_size=4, seed=3, num_epochs=1)
        got = list(stream)
        ref = list(_local(shards).batches(1))
        _assert_streams_equal(got, ref)
    finally:
        svc.close()
