"""ChaosProxy (ISSUE 15): every fault shape observable from a plain
client, seeded-schedule determinism, and the ChaosEngine wiring of the
net_* ACTIONS (hostless — they must not perturb the RNG victims of
other events)."""

import json
import socket
import threading
import time

import pytest

from tpucfn.ft.chaos import ChaosEngine, ChaosEvent, ChaosSpec, ChaosTarget
from tpucfn.net.proxy import ChaosProxy, NetFault, NetFaultSchedule
from tpucfn.obs.registry import MetricRegistry


class EchoServer:
    """Plain TCP echo upstream for the proxy to front."""

    def __init__(self):
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(16)
        self.sock.settimeout(0.25)
        self.received = bytearray()
        self._closed = threading.Event()
        threading.Thread(target=self._loop, daemon=True).start()

    @property
    def address(self) -> str:
        return f"127.0.0.1:{self.sock.getsockname()[1]}"

    def _loop(self):
        while not self._closed.is_set():
            try:
                conn, _ = self.sock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            conn.settimeout(5.0)
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn):
        while True:
            try:
                data = conn.recv(4096)
            except OSError:
                return
            if not data:
                return
            self.received.extend(data)
            try:
                conn.sendall(data)
            except OSError:
                return

    def close(self):
        self._closed.set()
        try:
            self.sock.close()
        except OSError:
            pass


@pytest.fixture
def echo():
    s = EchoServer()
    yield s
    s.close()


def _counted(proxy, *, up, down, timeout=10.0):
    """A pump counts a chunk after it has sent it, so a client can hold its
    echo before the proxy has counted those bytes, and an offset or a counter
    read then is short by them: wait until the proxy has counted what the
    client already holds."""
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        with proxy.state.lock:
            if (proxy._fwd_bytes["up"] >= up
                    and proxy._fwd_bytes["down"] >= down):
                return
        time.sleep(0.005)
    raise AssertionError(f"the proxy never counted {up} up, {down} down")


def _client(proxy, timeout=5.0):
    c = socket.create_connection(("127.0.0.1", proxy.port), timeout=5.0)
    c.settimeout(timeout)
    return c


def test_passthrough_is_byte_identical(echo):
    with ChaosProxy(echo.address) as p:
        c = _client(p)
        payload = bytes(range(256)) * 128
        c.sendall(payload)
        got = bytearray()
        while len(got) < len(payload):
            got.extend(c.recv(65536))
        assert bytes(got) == payload
        c.close()


def test_latency_delays_forwarding(echo):
    with ChaosProxy(echo.address) as p:
        c = _client(p)
        c.sendall(b"a")
        assert c.recv(1) == b"a"  # warm, no fault
        p.inject("latency", delay_s=0.3, duration_s=10.0)
        t0 = time.monotonic()
        c.sendall(b"b")
        assert c.recv(1) == b"b"
        assert time.monotonic() - t0 >= 0.3
        c.close()


def test_throttle_trickles_at_the_configured_rate(echo):
    with ChaosProxy(echo.address) as p:
        c = _client(p)
        p.inject("throttle", rate_bps=4000, duration_s=30.0)
        t0 = time.monotonic()
        c.sendall(b"x" * 2000)
        got = bytearray()
        while len(got) < 2000:
            got.extend(c.recv(4096))
        # 2000 B at 4000 B/s is ~0.5 s per direction; the two pipeline,
        # so the floor is one direction's trickle (minus the last tick)
        assert time.monotonic() - t0 >= 0.4
        c.close()


def test_stall_holds_the_connection_open_then_resumes(echo):
    with ChaosProxy(echo.address) as p:
        c = _client(p)
        c.sendall(b"a")
        assert c.recv(1) == b"a"
        p.inject("stall", duration_s=0.6)
        c.sendall(b"b")
        c.settimeout(0.25)
        with pytest.raises(socket.timeout):
            c.recv(1)  # stalled: NO bytes, NO FIN, NO RST
        c.settimeout(5.0)
        assert c.recv(1) == b"b"  # duration elapsed: resumed
        c.close()


def test_stall_after_bytes_arms_mid_stream(echo):
    with ChaosProxy(echo.address) as p:
        c = _client(p)
        # stall the DOWN direction after 4 more bytes flow down
        p.inject("stall", duration_s=10.0, direction="down", after_bytes=4)
        c.sendall(b"abcdefgh")
        got = c.recv(8)  # the armed threshold lets only 4 through
        while len(got) < 4:
            got += c.recv(8)
        assert got == b"abcd"
        c.settimeout(0.3)
        with pytest.raises(socket.timeout):
            c.recv(1)
        c.close()


def test_partition_drops_one_direction_only(echo):
    with ChaosProxy(echo.address) as p:
        c = _client(p)
        c.sendall(b"a")
        assert c.recv(1) == b"a"
        p.inject("partition", direction="up", duration_s=10.0)
        before = bytes(echo.received)
        c.sendall(b"zz")
        time.sleep(0.3)
        assert bytes(echo.received) == before  # upstream never saw it
        c.settimeout(0.3)
        with pytest.raises(socket.timeout):
            c.recv(1)  # nothing echoed, connection still open
        c.close()


def test_tear_forwards_exactly_after_bytes_then_closes(echo):
    with ChaosProxy(echo.address) as p:
        c = _client(p)
        c.sendall(b"hi")
        assert c.recv(2) == b"hi"
        _counted(p, up=2, down=2)  # the tear's offset counts from here
        p.inject("tear", after_bytes=7, direction="down")
        c.sendall(b"y" * 100)
        got = bytearray()
        try:
            while True:
                d = c.recv(100)
                if not d:
                    break
                got.extend(d)
        except OSError:
            pass  # a post-tear read may also surface as ECONNRESET
        assert len(got) == 7  # the torn frame: exactly N bytes, then cut
        c.close()
        # one-shot: the NEXT connection passes cleanly
        c2 = _client(p)
        c2.sendall(b"fresh")
        assert c2.recv(5) == b"fresh"
        c2.close()


def test_rst_resets_live_connections(echo):
    with ChaosProxy(echo.address) as p:
        c = _client(p)
        c.sendall(b"a")
        assert c.recv(1) == b"a"
        p.inject("rst")
        time.sleep(0.2)
        with pytest.raises(OSError):
            # the RST surfaces on the next recv (or the send, under
            # load) as ECONNRESET/EPIPE — never a quiet FIN
            if c.recv(1) == b"":
                raise ConnectionResetError("got FIN, wanted RST")
        c.close()


def test_clear_lifts_active_faults(echo):
    with ChaosProxy(echo.address) as p:
        c = _client(p)
        p.inject("stall", duration_s=60.0)
        p.clear()
        c.sendall(b"ok")
        assert c.recv(2) == b"ok"
        c.close()


# -- seeded schedules -------------------------------------------------------


def test_schedule_json_roundtrip_and_validation():
    sched = NetFaultSchedule(seed=42, faults=(
        NetFault(kind="throttle", at_s=1.0, rate_bps=512, duration_s=5.0),
        NetFault(kind="tear", at_s=2.0),
        NetFault(kind="clear", at_s=3.0),
    ))
    again = NetFaultSchedule.from_json(json.dumps(sched.to_json()))
    assert again == sched
    with pytest.raises(ValueError):
        NetFault(kind="flood")
    with pytest.raises(ValueError):
        NetFault(kind="stall", direction="sideways")
    with pytest.raises(ValueError):
        NetFault(kind="throttle")  # rate_bps required
    with pytest.raises(ValueError):
        NetFault(kind="latency")  # delay_s required


def test_seeded_schedule_is_deterministic(echo):
    """Same seed ⇒ same fault timeline, including RNG-resolved tear
    sizes; a different seed resolves differently (the draw is real)."""
    sched = NetFaultSchedule(seed=7, faults=(
        NetFault(kind="tear", at_s=0.0),
        NetFault(kind="tear", at_s=0.05),
    ))

    def run(seed):
        s = NetFaultSchedule(faults=sched.faults, seed=seed)
        with ChaosProxy(echo.address, schedule=s) as p:
            deadline = time.monotonic() + 5.0
            while len(p.fired) < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
            return [(f["kind"], f.get("after_bytes")) for f in p.fired]

    a, b = run(7), run(7)
    assert a == b and len(a) == 2
    assert all(k == "tear" and isinstance(n, int) for k, n in a)
    c = run(1234)
    assert [n for _, n in c] != [n for _, n in a]


def test_scheduled_tear_cuts_at_the_seeded_byte_count(echo):
    """The fault timeline is observable, not just logged: a client
    reading through a scheduled tear receives exactly the seeded byte
    count before the cut."""
    sched = NetFaultSchedule(seed=3, faults=(
        NetFault(kind="tear", at_s=0.0, direction="down"),))
    with ChaosProxy(echo.address, schedule=sched) as p:
        deadline = time.monotonic() + 5.0
        while not p.fired and time.monotonic() < deadline:
            time.sleep(0.01)
        n = p.fired[0]["after_bytes"]
        c = _client(p)
        c.sendall(b"q" * 500)
        got = bytearray()
        try:
            while True:
                d = c.recv(500)
                if not d:
                    break
                got.extend(d)
        except OSError:
            pass
        assert len(got) == n
        c.close()


def test_proxy_metrics_and_fired_audit_trail(echo):
    reg = MetricRegistry()
    with ChaosProxy(echo.address, registry=reg) as p:
        c = _client(p)
        c.sendall(b"abc")
        assert c.recv(3) == b"abc"
        p.inject("latency", delay_s=0.01, duration_s=1.0)
        c.close()
        _counted(p, up=3, down=3)
        v = reg.varz()["metrics"]
        assert v["net_proxy_connections_total"] == 1
        assert v["net_proxy_forwarded_bytes_total"] >= 6  # echo: up + down
        assert v["net_proxy_faults_fired_total"] == 1
        assert p.fired[0]["kind"] == "latency"


# -- ChaosEngine wiring -----------------------------------------------------


class NetRecorder(ChaosTarget):
    def __init__(self, hosts=2):
        self.hosts = hosts
        self.calls = []

    def num_hosts(self):
        return self.hosts

    def kill_host(self, host_id):
        self.calls.append(("kill", host_id))

    def net_fault(self, proxy, kind, *, duration_s, delay_s, rate_bps,
                  direction, after_bytes):
        self.calls.append(("net", proxy, kind, duration_s, delay_s,
                           rate_bps, direction, after_bytes))


def test_engine_dispatches_net_actions_with_params():
    spec = ChaosSpec(seed=0, events=(
        ChaosEvent(action="net_throttle", at_s=0.5, rate_bps=1024.0,
                   duration_s=3.0),
        ChaosEvent(action="net_stall", at_s=1.0, duration_s=2.0,
                   direction="down", after_bytes=64, host=1),
        ChaosEvent(action="net_clear", at_s=2.0),
    ))
    t = NetRecorder()
    eng = ChaosEngine(spec, t)
    eng.tick(0.6)
    eng.tick(1.1)
    eng.tick(2.1)
    assert t.calls == [
        ("net", None, "throttle", 3.0, 0.0, 1024.0, "both", None),
        ("net", 1, "stall", 2.0, 0.0, 0.0, "down", 64),
        ("net", None, "clear", 0.0, 0.0, 0.0, "both", None),
    ]
    assert eng.done()


def test_net_actions_are_hostless_for_the_victim_rng():
    """An unpinned net_* event must not draw from the seeded RNG — the
    kill after it must resolve the same victim with or without the net
    event in the spec (the kill_coordinator discipline)."""

    def victim(events):
        t = NetRecorder(hosts=8)
        ChaosEngine(ChaosSpec(seed=123, events=events), t).tick(10.0)
        return [c for c in t.calls if c[0] == "kill"]

    just_kill = victim((ChaosEvent(action="kill", at_s=1.0),))
    with_net = victim((ChaosEvent(action="net_rst", at_s=0.5),
                       ChaosEvent(action="net_tear", at_s=0.6),
                       ChaosEvent(action="kill", at_s=1.0)))
    assert just_kill == [c for c in with_net if c[0] == "kill"] == just_kill


def test_net_event_json_roundtrip_keeps_net_fields():
    ev = ChaosEvent(action="net_throttle", at_s=1.0, rate_bps=2048.0,
                    duration_s=5.0, direction="up", after_bytes=16)
    spec = ChaosSpec(events=(ev,), seed=9)
    again = ChaosSpec.from_json(json.dumps(spec.to_json()))
    assert again.events[0] == ev
    # defaults are elided from the JSON (spec files stay readable)
    j = ChaosEvent(action="net_rst", at_s=1.0).to_json()
    assert "rate_bps" not in j and "direction" not in j


def test_coordinator_net_fault_requires_registered_proxies():
    from tpucfn.ft.coordinator import GangCoordinator

    coord = GangCoordinator.__new__(GangCoordinator)
    coord.net_proxies = []
    with pytest.raises(ValueError, match="net_proxies"):
        coord.net_fault(None, "stall", duration_s=1.0, delay_s=0.0,
                        rate_bps=0.0, direction="both", after_bytes=None)


def test_coordinator_net_fault_routes_to_proxies(tmp_path, echo):
    from tpucfn.ft.coordinator import GangCoordinator

    class FakeProxy:
        def __init__(self):
            self.calls = []

        def inject(self, kind, **kw):
            self.calls.append((kind, kw))

        def clear(self):
            self.calls.append(("clear", {}))

    a, b = FakeProxy(), FakeProxy()
    coord = GangCoordinator.__new__(GangCoordinator)
    coord.net_proxies = [a, b]
    coord.ft_dir = None  # _event no-ops
    coord.net_fault(None, "latency", duration_s=1.0, delay_s=0.2,
                    rate_bps=0.0, direction="both", after_bytes=None)
    assert len(a.calls) == 1 and len(b.calls) == 1
    coord.net_fault(1, "clear", duration_s=0.0, delay_s=0.0,
                    rate_bps=0.0, direction="both", after_bytes=None)
    assert len(a.calls) == 1 and a.calls[0][0] == "latency"
    assert b.calls[-1][0] == "clear"
    with pytest.raises(ValueError, match="out of range"):
        coord.net_fault(5, "stall", duration_s=0.0, delay_s=0.0,
                        rate_bps=0.0, direction="both", after_bytes=None)


def test_net_event_params_validate_at_spec_construction():
    """Review fix: a bad net_* spec must fail at PARSE time (rc 2 /
    ValueError at build), never unwind the live coordinator when the
    event fires mid-run."""
    with pytest.raises(ValueError, match="delay_s"):
        ChaosEvent(action="net_latency", at_s=1.0)
    with pytest.raises(ValueError, match="rate_bps"):
        ChaosEvent(action="net_throttle", at_s=1.0)
    # stall/tear/rst/partition/clear have no mandatory params
    ChaosEvent(action="net_stall", at_s=1.0)
    ChaosEvent(action="net_clear", at_s=1.0)


def test_stalled_pump_exits_on_proxy_close(echo):
    """Review fix: an unbounded stall armed mid-chunk must not leave a
    pump thread spinning forever after close()."""
    import threading as _threading

    before = _threading.active_count()
    p = ChaosProxy(echo.address).start()
    c = _client(p)
    c.sendall(b"a")
    assert c.recv(1) == b"a"
    # until-cleared stall armed 2 bytes into the next downstream chunk:
    # the pump holds a mid-chunk remainder when close() lands
    p.inject("stall", duration_s=0.0, direction="down", after_bytes=2)
    c.sendall(b"xyzw")
    time.sleep(0.3)
    p.close()
    c.close()
    deadline = time.monotonic() + 5.0
    while _threading.active_count() > before and time.monotonic() < deadline:
        time.sleep(0.05)
    assert _threading.active_count() <= before, \
        "pump thread leaked past ChaosProxy.close()"
