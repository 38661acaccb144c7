"""Multi-host serving control plane: process 0 leads, the rest follow.

A multi-host slice is ONE jax.distributed world (parallel/distributed.py)
— every process must dispatch the SAME compiled programs in the SAME
order or the SPMD collectives deadlock. The reference never faces this:
its replicas are independent single-host servers (SURVEY.md §2.3). Here:

- **process 0** runs the full HTTP server + scheduler. Its engine is
  wrapped in :class:`MirroredEngine`, which broadcasts every
  device-dispatching call (admit / extend / decode_n / release / masks /
  warm) over a TCP control stream BEFORE executing it locally.
- **processes 1..n-1** run :func:`run_follower`: connect to process 0's
  control port, then replay the stream — load the same model from their
  own store (the StatefulSet init container pulled it), build the same
  Engine, execute the same calls with the same (replicated) arguments.
  Ordering is the socket's FIFO; synchronisation is the collectives
  themselves.

All host-side decision state is deterministic by construction: prompt
buckets, page tables, penalty windows, and PRNG seeds derive from the
call arguments alone (engine.py avoids per-process `hash()`), so replayed
calls produce byte-identical device programs and inputs.

Admission/fairness policy state (priority queues, WDRR deficits, tenant
rate buckets, the TTFT queue model — runtime/admission.py) lives on
process 0 ONLY: followers see just the admit/extend/decode calls that
survive admission. Policy decisions must never enter the broadcast
stream — they depend on wall-clock throughput observations that differ
per process and would desynchronise the replay.

The control port is the jax.distributed coordinator's port + 1, rendered
by the operator as TPU_DIST_CONTROL (operator/pod.py).
"""

from __future__ import annotations

import os
import pickle
import socket
import struct
import sys
import threading
import time
import weakref
from typing import Any, List, Optional

from ..server.metrics import GLOBAL as METRICS
from .errors import FollowerLost
from .faults import FAULTS, InjectedFault
# flight-recorder events here are strictly host-side observability —
# they never enter the broadcast stream, so leader tracing can never
# desync a follower's replay (each process records into its OWN ring)
from .trace import FLIGHT

CONTROL_PORT_OFFSET = 1      # coordinator port + 1

# live control planes for the follower-lag gauge: weakly held so a
# torn-down leader doesn't pin a stale series (same pattern as the
# gateway's per-state replica gauges)
_LIVE_CPS: "weakref.WeakSet[ControlPlane]" = weakref.WeakSet()
METRICS.gauge_fn(
    "tpu_model_follower_lag_seconds",
    lambda: max((cp.lag_s for cp in _LIVE_CPS), default=0.0))


def log(msg: str) -> None:
    print(f"follower-cp: {msg}", file=sys.stderr, flush=True)


def _send(sock: socket.socket, obj: Any) -> None:
    data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    sock.sendall(struct.pack(">I", len(data)) + data)


def _recv(sock: socket.socket) -> Any:
    hdr = b""
    while len(hdr) < 4:
        chunk = sock.recv(4 - len(hdr))
        if not chunk:
            raise ConnectionError("control stream closed")
        hdr += chunk
    n = struct.unpack(">I", hdr)[0]
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(min(1 << 20, n - len(buf)))
        if not chunk:
            raise ConnectionError("control stream closed mid-message")
        buf += chunk
    return pickle.loads(bytes(buf))


class ControlPlane:
    """Process 0's broadcast channel to the followers."""

    def __init__(self, n_followers: int, port: int, bind: str = "0.0.0.0",
                 heartbeat_s: Optional[float] = None):
        self.n = n_followers
        # serializes broadcast+local-dispatch pairs: the follower replays
        # the stream single-threaded in FIFO order, so every leader
        # thread that dispatches SPMD programs (scheduler decode loop,
        # HTTP embed threads, unload) must enter the stream AND the
        # device queue in the same order — holding this lock across both
        # is what guarantees it
        self.dispatch_lock = threading.RLock()
        self._conns: List[socket.socket] = []
        self._lock = threading.Lock()
        self._ready = threading.Event()
        # set on the first failed send: once any follower is gone the
        # SPMD world cannot make progress (a collective would hang), so
        # every later broadcast fails fast with FollowerLost instead of
        # half-dispatching and desyncing the survivors
        self.degraded = False
        self.degraded_reason: Optional[str] = None
        # bounded send backpressure: a follower whose TCP buffer stays
        # full for longer than this is DEAD, not slow — without the bound
        # one stalled host wedges every dispatch forever. Sends that
        # complete but slowly are the SLOW case: dispatch proceeds and
        # the lag shows up in tpu_model_follower_lag_seconds.
        self.send_timeout_s = float(
            os.environ.get("TPU_CP_SEND_TIMEOUT_S", "20"))
        self.lag_s = 0.0         # slowest send in the latest broadcast
        self._hb_stop = threading.Event()
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((bind, port))
        self._srv.listen(n_followers)
        threading.Thread(target=self._accept_loop, daemon=True).start()
        log(f"awaiting {n_followers} follower(s) on :{port}")
        # idle-path failure detection: a dead follower pod otherwise goes
        # unnoticed until the next real dispatch blocks a request. 0
        # disables (tests drive broadcast() directly).
        if heartbeat_s is None:
            heartbeat_s = float(os.environ.get("TPU_CP_HEARTBEAT_S", "10"))
        self.heartbeat_s = heartbeat_s
        if heartbeat_s > 0:
            threading.Thread(target=self._heartbeat_loop,
                             daemon=True).start()
        _LIVE_CPS.add(self)

    def _accept_loop(self):
        while len(self._conns) < self.n:
            try:
                conn, addr = self._srv.accept()
            except OSError:     # listener closed during shutdown
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            if self.send_timeout_s > 0:
                conn.settimeout(self.send_timeout_s)
            with self._lock:
                self._conns.append(conn)
            log(f"follower connected from {addr} "
                f"({len(self._conns)}/{self.n})")
        self._ready.set()

    def _heartbeat_loop(self):
        self._ready.wait()
        while not self._hb_stop.wait(self.heartbeat_s):
            try:
                with self.dispatch_lock:
                    # the heartbeat rides the same FIFO stream as
                    # mirrored ops — holding dispatch_lock across the
                    # send IS the ordering guarantee
                    # lint: allow(lock-order): FIFO heartbeat send by design
                    self.broadcast(("ping",))
            except FollowerLost:
                return          # degraded is set; nothing left to probe

    def _mark_degraded(self, reason: str) -> FollowerLost:
        if not self.degraded:
            self.degraded = True
            self.degraded_reason = reason
            METRICS.inc("tpu_model_followers_lost_total")
            FLIGHT.record("follower_lost", reason=reason[:200])
            log(f"DEGRADED: {reason}")
        return FollowerLost(reason)

    def broadcast(self, msg: tuple) -> None:
        """FIFO broadcast; blocks until the full follower set has joined
        (a call dispatched before the world is complete would desync).
        A send failure closes the dead conn, marks the world degraded,
        and raises :class:`FollowerLost` — the typed error surfaces to
        the caller instead of a half-dispatched desync."""
        if self.degraded:
            raise FollowerLost(
                f"control plane degraded: {self.degraded_reason}")
        self._ready.wait()
        with self._lock:
            worst = 0.0
            for c in list(self._conns):
                t0 = time.monotonic()
                try:
                    FAULTS.check("follower.send")
                    # serialising sends under _lock is the point — the
                    # per-follower byte streams must not interleave; the
                    # per-conn send timeout (TPU_CP_SEND_TIMEOUT_S) is
                    # the backpressure bound, so a stalled follower can
                    # block a dispatch for at most one window
                    # lint: allow(lock-order): frame send serialised by design
                    _send(c, msg)
                except (OSError, InjectedFault) as e:
                    try:
                        c.close()
                    except OSError:
                        pass
                    self._conns.remove(c)
                    if isinstance(e, socket.timeout):
                        # slow-vs-dead verdict: the kernel buffer stayed
                        # full for the whole window — that is a dead (or
                        # unrecoverably wedged) host, not a slow one
                        raise self._mark_degraded(
                            f"follower send exceeded the "
                            f"{self.send_timeout_s:.0f}s backpressure "
                            f"bound: {e}") from e
                    raise self._mark_degraded(
                        f"send to follower failed: {e}") from e
                worst = max(worst, time.monotonic() - t0)
            # slow-but-alive: the send completed within the bound; the
            # lag gauge is how operators see a follower eating into the
            # backpressure window before it ever trips the bound
            self.lag_s = worst

    def close(self):
        self._hb_stop.set()
        with self._lock:
            for c in self._conns:
                try:
                    c.close()
                except OSError:
                    pass
            self._conns.clear()
        try:
            self._srv.close()
        except OSError:
            pass


class MirroredEngine:
    """Engine proxy for process 0: broadcast-then-execute for every call
    that dispatches a device program or mutates replay-relevant host
    state (page tables). Everything else delegates transparently."""

    MIRRORED = ("admit", "admit_many", "extend", "decode", "decode_n",
                # the launched forms of the three admissions: followers
                # replay launches and never wait, so a handle the leader
                # collects later keeps every host's call stream the same
                "admit_launch", "admit_many_launch", "extend_launch",
                # rollback_lengths takes a frozen device-grammar slot's
                # host-length overshoot back at the call-stream position
                # the leader fanned the chunk out, so followers never
                # need to wait a handle to stay bit-identical
                "decode_n_launch", "rollback_lengths", "release",
                "set_mask", "clear_mask", "install_grammar",
                "warm_buckets", "free_slot_pages", "prepare_decode",
                # radix prefix cache: stitching/donation/eviction mutate
                # page refcounts and (for COW) dispatch a page copy, so
                # every host must replay them in order; prefix_probe is
                # read-only and deliberately NOT mirrored
                "stitch", "donate_prefix", "radix_evict", "radix_reset",
                # tier-2 prefix snapshot install mutates the radix tree
                # and the host arena (replay-relevant: later stitches
                # branch on tier state); export_prefixes is read-only
                # and deliberately NOT mirrored
                "import_prefixes",
                # epoch fence: quiesce blocks on each host's OWN devices
                # and drains that host's quarantine — replayed at the
                # same call-stream position, every host's free list
                # stays bit-identical (DecodeHandle.wait, which followers
                # never run, deliberately does NOT retire epochs)
                "fence_quiesce",
                # fence_retire moves the retired epoch as a launch's
                # retire= does, with no launch: host state alone, at a
                # fixed place of the leader's step
                "fence_retire")

    def __init__(self, inner, cp: ControlPlane):
        object.__setattr__(self, "_inner", inner)
        object.__setattr__(self, "_cp", cp)

    def __getattr__(self, name):
        value = getattr(self._inner, name)
        if name in self.MIRRORED:
            cp = self._cp

            def mirrored(*a, __value=value, __name=name, **kw):
                with cp.dispatch_lock:
                    cp.broadcast(("call", __name, a, kw))
                    return __value(*a, **kw)
            return mirrored
        return value


def control_address(env=None) -> Optional[tuple]:
    """(host, port) of the control stream, from the operator env:
    TPU_DIST_CONTROL if present, else coordinator host at port+1."""
    import os
    e = env if env is not None else os.environ
    ctl = e.get("TPU_DIST_CONTROL")
    if ctl:
        host, _, port = ctl.rpartition(":")
        return host, int(port)
    coord = e.get("TPU_DIST_COORDINATOR")
    if not coord:
        return None
    host, _, port = coord.rpartition(":")
    return host, int(port) + CONTROL_PORT_OFFSET


def run_follower(manager, host: str, port: int,
                 health_port: Optional[int] = None) -> None:
    """Replay the leader's stream forever (process_index > 0).

    ``manager`` is a follower-mode ModelManager (server/app.py): load()
    builds a bare Engine — no scheduler, no HTTP app — against the same
    store this pod's init container populated."""
    if health_port:
        _serve_health(health_port)
    sock = None
    for attempt in range(240):       # leader may still be compiling
        try:
            sock = socket.create_connection((host, port), timeout=10)
            break
        except OSError:
            time.sleep(2.0)
    if sock is None:
        raise ConnectionError(f"leader control port {host}:{port} "
                              f"unreachable")
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    # silent-leader watchdog: the leader's heartbeat guarantees traffic
    # every TPU_CP_HEARTBEAT_S, so a recv gap past this bound means the
    # leader is dead or partitioned away. Fail static to a CLEAN exit
    # instead of hanging on the broadcast socket forever — the pod
    # restarts and rejoins the next world. 0 disables (tests drive the
    # stream by hand).
    leader_timeout_s = float(os.environ.get("TPU_CP_LEADER_TIMEOUT_S",
                                            "60"))
    if leader_timeout_s > 0:
        sock.settimeout(leader_timeout_s)
    log(f"joined control stream {host}:{port}")
    engine = None
    while True:
        try:
            msg = _recv(sock)
        except socket.timeout:
            # lint: allow(follower-purity): own per-process metrics — local observability, never broadcast back
            METRICS.inc("tpu_model_leader_lost_total")
            # lint: allow(follower-purity): own per-process flight ring — local diagnosis, never broadcast back
            FLIGHT.record("leader_lost", timeout_s=leader_timeout_s)
            log(f"leader silent for {leader_timeout_s:g}s "
                f"(TPU_CP_LEADER_TIMEOUT_S) — failing static, clean exit")
            return
        op = msg[0]
        if op == "ping":
            continue             # leader heartbeat; liveness only
        if op == "load":
            lm = manager.load(msg[1])
            engine = lm.engine
            log(f"loaded {msg[1]}")
        elif op == "unload":
            manager.unload_now()
            engine = None
        elif op == "lm_call":
            _, method, a = msg
            try:
                getattr(manager.loaded, method)(*a)
            except Exception as e:   # noqa: BLE001
                log(f"replayed lm {method} raised {type(e).__name__}: {e}")
        elif op == "call":
            _, method, a, kw = msg
            try:
                getattr(engine, method)(*a, **kw)
            except Exception as e:   # noqa: BLE001
                # deterministic failures (PagesExhausted, too-long prompt)
                # happen on the leader too, BEFORE any device dispatch —
                # replaying them (incl. their page-table side effects)
                # keeps host state in lockstep; anything else will show
                # up here loudly and then desync visibly
                # lint: allow(follower-purity): own per-process flight ring — local diagnosis, never broadcast back
                FLIGHT.record("replay_error", method=method,
                              error=f"{type(e).__name__}: {e}"[:200])
                log(f"replayed {method} raised {type(e).__name__}: {e}")
        elif op == "shutdown":
            log("leader shut down")
            return
        else:
            raise ValueError(f"unknown control op {op!r}")


def _serve_health(port: int) -> None:
    """Minimal /healthz endpoint so the follower pod's readinessProbe
    (same template as the leader's) reports Ready."""
    import http.server

    class H(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Type", "text/plain")
            self.end_headers()
            self.wfile.write(b"ok")

        def log_message(self, *a):
            pass

    srv = http.server.ThreadingHTTPServer(("0.0.0.0", port), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
