"""Multi-host control plane unit tier (runtime/follower.py): framing,
FIFO broadcast, broadcast-before-execute ordering, address resolution.
The full 2-process serving e2e lives in
tests/test_compose_e2e.py::test_multihost_model_cr_serves."""

import socket
import threading

import numpy as np

from ollama_operator_tpu.runtime import follower as F


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_framing_roundtrip():
    a, b = socket.socketpair()
    msgs = [("load", "m:latest"),
            ("call", "admit", (np.arange(5, dtype=np.int32),), {}),
            ("lm_call", "embed", (["x" * 5000],)),
            ("unload",)]
    for m in msgs:
        F._send(a, m)
    for m in msgs:
        got = F._recv(b)
        assert got[0] == m[0]
        if m[0] == "call":
            np.testing.assert_array_equal(got[2][0], m[2][0])
    a.close()
    try:
        F._recv(b)
        raise AssertionError("expected ConnectionError on closed stream")
    except ConnectionError:
        pass
    b.close()


def test_control_plane_fifo_and_ready_gate():
    port = _free_port()
    cp = F.ControlPlane(2, port, bind="127.0.0.1")
    sent = []

    def producer():
        for i in range(50):
            cp.broadcast(("call", "decode_n", (i,), {}))
            sent.append(i)

    t = threading.Thread(target=producer)
    t.start()
    # broadcast must BLOCK until both followers join (a call dispatched
    # into a partial world would desync the SPMD programs)
    assert not sent, "broadcast ran before the follower set was complete"
    c1 = socket.create_connection(("127.0.0.1", port))
    assert not sent
    c2 = socket.create_connection(("127.0.0.1", port))
    t.join(timeout=10)
    assert len(sent) == 50
    for conn in (c1, c2):
        got = [F._recv(conn)[2][0] for _ in range(50)]
        assert got == list(range(50))      # FIFO, no loss, per follower
        conn.close()
    cp.close()


def test_mirrored_engine_broadcasts_before_execute():
    events = []

    class FakeCP:
        dispatch_lock = threading.RLock()

        def broadcast(self, msg):
            events.append(("bcast", msg[1]))

    class FakeEngine:
        n_slots = 4

        def decode_n(self, n=None):
            events.append(("exec", "decode_n"))
            return "toks"

        def admissible(self, n):
            return True

    me = F.MirroredEngine(FakeEngine(), FakeCP())
    assert me.decode_n(8) == "toks"
    assert events == [("bcast", "decode_n"), ("exec", "decode_n")]
    # non-mirrored attributes delegate without broadcasting
    assert me.n_slots == 4 and me.admissible(3) is True
    assert len(events) == 2


def test_a_launched_admission_is_mirrored_and_its_wait_is_not():
    """The leader's scheduler launches an admission through the mirror and
    collects its token later: the launch rides the call stream (followers
    replay it and never wait), the handle's wait does not, and a real
    leader/follower pair ends with the same host state and token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ollama_operator_tpu.models import config as cfglib
    from ollama_operator_tpu.models import decoder
    from ollama_operator_tpu.runtime.engine import Engine, EngineConfig

    for name in ("admit_launch", "admit_many_launch", "extend_launch"):
        assert name in F.MirroredEngine.MIRRORED
    cfg = cfglib.PRESETS["tiny"]
    params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jnp.float32)
    ecfg = EngineConfig(max_slots=2, max_seq_len=64,
                        cache_dtype=jnp.float32, min_prefill_bucket=16)
    leader, follower = Engine(cfg, params, ecfg=ecfg), \
        Engine(cfg, params, ecfg=ecfg)
    calls = []

    class ReplayCP:
        dispatch_lock = threading.RLock()

        def broadcast(self, msg):
            _, name, a, kw = msg
            calls.append(name)
            getattr(follower, name)(*a, **kw)     # replayed, never waited

    me = F.MirroredEngine(leader, ReplayCP())
    ids = np.arange(1, 10, dtype=np.int32)
    handle = me.admit_launch(0, ids)
    chunk = me.decode_n_launch(4)
    first = handle.wait()                         # the leader alone waits
    toks = chunk.wait()
    assert calls == ["admit_launch", "decode_n_launch"]
    assert follower.active[0] and (follower._host_lengths
                                   == leader._host_lengths).all()
    # the follower's device state is the leader's: its own next chunk
    # gives what the leader's gives
    np.testing.assert_array_equal(np.asarray(follower.decode_n(2))[:, 0],
                                  np.asarray(leader.decode_n(2))[:, 0])
    assert len(first) == 1 and toks.shape == (4, 2)


def test_control_address_resolution():
    assert F.control_address({"TPU_DIST_CONTROL": "sts-0.svc:8477"}) == \
        ("sts-0.svc", 8477)
    assert F.control_address(
        {"TPU_DIST_COORDINATOR": "sts-0.svc:8476"}) == ("sts-0.svc", 8477)
    assert F.control_address({}) is None


def test_dead_follower_marks_degraded_and_raises():
    """A send to a closed follower socket raises typed FollowerLost and
    marks the world degraded; later broadcasts fail FAST (no blocking on
    a half-dead world) until the pod is restarted."""
    import pytest
    from ollama_operator_tpu.runtime.errors import FollowerLost
    from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

    lost_before = METRICS.get("tpu_model_followers_lost_total")
    port = _free_port()
    cp = F.ControlPlane(1, port, bind="127.0.0.1", heartbeat_s=0)
    c1 = socket.create_connection(("127.0.0.1", port))
    cp.broadcast(("call", "decode_n", (1,), {}))
    assert F._recv(c1)[1] == "decode_n"
    c1.close()
    try:
        # closed peer: first or second send hits the broken pipe (the
        # first may land in the kernel buffer before the RST arrives)
        with pytest.raises(FollowerLost):
            for _ in range(50):
                cp.broadcast(("call", "decode_n", (2,), {}))
        assert cp.degraded
        assert cp.degraded_reason
        assert METRICS.get("tpu_model_followers_lost_total") \
            == lost_before + 1
        # degraded world: fail fast, don't half-dispatch
        with pytest.raises(FollowerLost):
            cp.broadcast(("ping",))
        # counted once, not per failed broadcast
        assert METRICS.get("tpu_model_followers_lost_total") \
            == lost_before + 1
    finally:
        cp.close()


def test_follower_send_fault_marks_degraded():
    """The follower.send fault point drives the same degraded path as a
    real socket error — InjectedFault is caught like OSError."""
    import pytest
    from ollama_operator_tpu.runtime.errors import FollowerLost
    from ollama_operator_tpu.runtime.faults import FAULTS

    port = _free_port()
    cp = F.ControlPlane(1, port, bind="127.0.0.1", heartbeat_s=0)
    c1 = socket.create_connection(("127.0.0.1", port))
    try:
        FAULTS.arm("follower.send", "fail:once")
        with pytest.raises(FollowerLost):
            cp.broadcast(("call", "decode_n", (1,), {}))
        assert cp.degraded
    finally:
        c1.close()
        cp.close()


def test_heartbeat_pings_and_follower_ignores_them():
    """The leader's heartbeat thread broadcasts pings; a follower's op
    loop must treat them as liveness-only no-ops between real ops."""
    port = _free_port()
    cp = F.ControlPlane(1, port, bind="127.0.0.1", heartbeat_s=0.02)
    c1 = socket.create_connection(("127.0.0.1", port))
    try:
        got = [F._recv(c1) for _ in range(3)]
        assert ("ping",) in [tuple(m[:1]) for m in got] or \
            all(m[0] == "ping" for m in got)
        # interleave a real broadcast between pings: FIFO preserved
        cp.broadcast(("call", "decode_n", (7,), {}))
        while True:
            m = F._recv(c1)
            if m[0] != "ping":
                break
        assert m[0] == "call" and m[2][0] == 7
    finally:
        c1.close()
        cp.close()


def test_silent_leader_fails_static_with_clean_exit(monkeypatch):
    """Partition drill: a follower whose leader goes silent past
    TPU_CP_LEADER_TIMEOUT_S must fail static — count the loss, leave a
    breadcrumb, and EXIT cleanly (the pod restarts and rejoins the next
    world) instead of hanging on the broadcast socket forever."""
    from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

    monkeypatch.setenv("TPU_CP_LEADER_TIMEOUT_S", "0.3")
    lost_before = METRICS.get("tpu_model_leader_lost_total")
    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    accepted = []

    def accept():
        conn, _ = srv.accept()
        accepted.append(conn)    # accept the join, then say nothing

    threading.Thread(target=accept, daemon=True).start()
    t = threading.Thread(target=F.run_follower,
                         args=(None, "127.0.0.1", port), daemon=True)
    t.start()
    t.join(timeout=5)
    try:
        assert not t.is_alive(), "follower must fail static, not hang"
        assert METRICS.get("tpu_model_leader_lost_total") \
            == lost_before + 1
    finally:
        for c in accepted:
            c.close()
        srv.close()


def test_slow_follower_trips_backpressure_bound(monkeypatch):
    """Slow-vs-dead verdict: a follower that stops draining its socket
    wedges a dispatch for at most one TPU_CP_SEND_TIMEOUT_S window, then
    the world degrades with the typed backpressure diagnosis."""
    import time as _time

    import pytest
    from ollama_operator_tpu.runtime.errors import FollowerLost

    monkeypatch.setenv("TPU_CP_SEND_TIMEOUT_S", "0.3")
    port = _free_port()
    cp = F.ControlPlane(1, port, bind="127.0.0.1", heartbeat_s=0)
    c1 = socket.create_connection(("127.0.0.1", port))
    big = ("call", "embed", (b"x" * (1 << 20),), {})
    t0 = _time.monotonic()
    try:
        with pytest.raises(FollowerLost) as ei:
            # never read from c1: the kernel buffers fill and the send
            # window expires on a wedged — not merely slow — peer
            for _ in range(64):
                cp.broadcast(big)
        assert "backpressure bound" in str(ei.value)
        assert cp.degraded
        assert _time.monotonic() - t0 < 10
    finally:
        c1.close()
        cp.close()


def test_follower_lag_gauge_reports_worst_live_lag():
    """Sends that complete within the bound are the SLOW case: dispatch
    proceeds and the lag surfaces in tpu_model_follower_lag_seconds so
    operators see a follower eating into the backpressure window."""
    from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

    port = _free_port()
    cp = F.ControlPlane(1, port, bind="127.0.0.1", heartbeat_s=0)
    c1 = socket.create_connection(("127.0.0.1", port))
    try:
        cp.broadcast(("ping",))
        assert cp.lag_s >= 0.0
        cp.lag_s = 1.25        # the gauge reads live control planes
        samples = [ln for ln in METRICS.render().splitlines()
                   if ln.startswith("tpu_model_follower_lag_seconds")]
        assert samples, "lag gauge missing from the scrape"
        assert max(float(ln.split()[-1]) for ln in samples) >= 1.25
    finally:
        c1.close()
        cp.close()


def test_heartbeat_detects_silent_follower_death():
    """With no traffic at all, the heartbeat alone must discover a dead
    follower and flip the world degraded — this is the watchdog that
    turns a wedged follower into a fast typed failure."""
    port = _free_port()
    cp = F.ControlPlane(1, port, bind="127.0.0.1", heartbeat_s=0.02)
    c1 = socket.create_connection(("127.0.0.1", port))
    import time as _time
    # wait until the heartbeat has started flowing, then kill the peer
    F._recv(c1)
    c1.close()
    deadline = _time.monotonic() + 5
    while not cp.degraded and _time.monotonic() < deadline:
        _time.sleep(0.01)
    try:
        assert cp.degraded
    finally:
        cp.close()
