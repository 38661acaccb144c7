"""Manager + HTTP-client tests.

Tier (b) of SURVEY.md §4's pyramid: the stdlib KubeClient speaks to the
fake apiserver over REAL HTTP (wire format, error mapping, chunked watch),
and the Manager's watch→queue→reconcile loop drives a Model to Available
end-to-end, with a kubelet-player thread flipping readiness — the closest
analog to envtest's "real API, fake kubelet" the reference relies on.
"""

import threading
import time

import pytest

from ollama_operator_tpu.operator import workload
from ollama_operator_tpu.operator.client import Conflict, KubeClient, NotFound
from ollama_operator_tpu.operator.manager import (LeaderElector, Manager,
                                                  WorkQueue)
from ollama_operator_tpu.operator.reconciler import is_condition_true
from ollama_operator_tpu.operator.types import API_VERSION, KIND

from fake_kube import FakeKube, serve_http


@pytest.fixture()
def fake():
    return FakeKube()


@pytest.fixture()
def http_client(fake):
    httpd = serve_http(fake)
    addr = f"http://127.0.0.1:{httpd.server_address[1]}"
    yield KubeClient(addr, timeout=5)
    httpd.shutdown()


def model_obj(name="phi", **spec):
    spec.setdefault("image", "phi")
    spec.setdefault("runtime", "cpu")
    return {"apiVersion": API_VERSION, "kind": KIND,
            "metadata": {"name": name, "namespace": "default"},
            "spec": spec}


class TestHttpClient:
    def test_crud_roundtrip(self, http_client):
        created = http_client.create(model_obj())
        assert created["metadata"]["resourceVersion"]
        got = http_client.get(API_VERSION, KIND, "default", "phi")
        assert got["spec"]["image"] == "phi"
        got["spec"]["replicas"] = 2
        updated = http_client.update(got)
        assert updated["spec"]["replicas"] == 2
        assert http_client.get(API_VERSION, KIND, "default", "ghost") is None
        http_client.delete(API_VERSION, KIND, "default", "phi")
        assert http_client.get(API_VERSION, KIND, "default", "phi") is None

    def test_status_subresource_is_separate(self, http_client):
        http_client.create(model_obj())
        m = http_client.get(API_VERSION, KIND, "default", "phi")
        m["status"] = {"replicas": 3}
        http_client.update_status(m)
        # spec update must not clobber status, and vice versa
        m = http_client.get(API_VERSION, KIND, "default", "phi")
        m["spec"]["replicas"] = 5
        http_client.update(m)
        m = http_client.get(API_VERSION, KIND, "default", "phi")
        assert m["status"]["replicas"] == 3 and m["spec"]["replicas"] == 5

    def test_conflict_and_duplicate_create(self, http_client):
        http_client.create(model_obj())
        with pytest.raises(Conflict):
            http_client.create(model_obj())
        stale = http_client.get(API_VERSION, KIND, "default", "phi")
        fresh = http_client.get(API_VERSION, KIND, "default", "phi")
        fresh["spec"]["replicas"] = 2
        http_client.update(fresh)
        stale["spec"]["replicas"] = 9
        with pytest.raises(Conflict):
            http_client.update(stale)

    def test_list_with_label_selector(self, http_client, fake):
        a = model_obj("a")
        a["metadata"]["labels"] = {"tier": "prod"}
        http_client.create(a)
        http_client.create(model_obj("b"))
        items = http_client.list(API_VERSION, KIND, "default",
                                 label_selector="tier=prod")
        assert [i["metadata"]["name"] for i in items] == ["a"]

    def test_watch_streams_events(self, http_client, fake):
        stop = threading.Event()
        seen = []

        def consume():
            for evt in http_client.watch(API_VERSION, KIND, "default",
                                         stop=stop):
                seen.append((evt["type"],
                             evt["object"]["metadata"]["name"]))
                if len(seen) >= 2:
                    return

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        time.sleep(0.3)  # watcher registers
        fake.create(model_obj("w1"))
        fake.create(model_obj("w2"))
        t.join(timeout=5)
        stop.set()
        assert ("ADDED", "w1") in seen and ("ADDED", "w2") in seen


class TestWorkQueue:
    def test_dedupe(self):
        q = WorkQueue()
        q.add(("ns", "a"))
        q.add(("ns", "a"))
        q.add(("ns", "b"))
        assert q.get(timeout=1) == ("ns", "a")
        assert q.get(timeout=1) == ("ns", "b")
        assert q.get(timeout=0.1) is None

    def test_delay_ordering_and_supersede(self):
        q = WorkQueue()
        q.add(("ns", "slow"), delay=5.0)
        q.add(("ns", "fast"), delay=0.0)
        assert q.get(timeout=1) == ("ns", "fast")
        q.add(("ns", "slow"), delay=0.0)  # sooner wins
        assert q.get(timeout=1) == ("ns", "slow")
        assert q.get(timeout=0.1) is None


class TestLeaderElection:
    def test_single_holder(self, fake):
        a = LeaderElector(fake, "default", identity="a", lease_seconds=2)
        b = LeaderElector(fake, "default", identity="b", lease_seconds=2)
        assert a._try_acquire() is True
        assert b._try_acquire() is False
        lease = fake.get("coordination.k8s.io/v1", "Lease", "default",
                         a.name)
        assert lease["spec"]["holderIdentity"] == "a"

    def test_takeover_after_expiry(self, fake):
        a = LeaderElector(fake, "default", identity="a", lease_seconds=1)
        assert a._try_acquire()
        lease = fake.get("coordination.k8s.io/v1", "Lease", "default",
                         a.name)
        lease["spec"]["renewTime"] = "2000-01-01T00:00:00.0000000Z"
        fake.update(lease)
        b = LeaderElector(fake, "default", identity="b", lease_seconds=1)
        assert b._try_acquire() is True


def play_kubelet(fake, stop):
    """Flip readiness of everything the reconciler creates."""
    while not stop.is_set():
        for sts in fake.list("apps/v1", "StatefulSet", "default"):
            n = sts["spec"].get("replicas", 1)
            if (sts.get("status") or {}).get("readyReplicas") != n:
                fake.set_status("apps/v1", "StatefulSet", "default",
                                sts["metadata"]["name"],
                                {"readyReplicas": n, "replicas": n})
        for dep in fake.list("apps/v1", "Deployment", "default"):
            n = dep["spec"].get("replicas", 1)
            if (dep.get("status") or {}).get("readyReplicas") != n:
                fake.set_status("apps/v1", "Deployment", "default",
                                dep["metadata"]["name"],
                                {"replicas": n, "readyReplicas": n,
                                 "availableReplicas": n})
        for svc in fake.list("v1", "Service", "default"):
            if not svc["spec"].get("clusterIP"):
                svc["spec"]["clusterIP"] = "10.0.0.9"
                try:
                    fake.update(svc)
                except Conflict:
                    pass
        stop.wait(0.05)


def _until(cond, what, guard=120.0):
    """Wait on the condition itself. ``guard`` only stops a hang: it is
    far above anything a healthy run needs and is not what the test
    measures."""
    deadline = time.monotonic() + guard
    while time.monotonic() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(0.02)
    raise AssertionError(what)


@pytest.fixture()
def fast_poll(monkeypatch):
    """The reconciler's steady-state requeue, shrunk. A Model reaches
    Available over three POLL results, and the manager backs consecutive
    POLLs off (5 s, 7.5 s, 11.25 s: 24 s on an idle host), so a clock of
    30 s measured the host's load, not the watch path. Below the
    manager's POLL_BACKOFF_FLOOR nothing backs off."""
    import ollama_operator_tpu.operator.reconciler as r
    monkeypatch.setattr(r, "POLL", r.Result(requeue_after=0.1))


class TestManagerEndToEnd:
    def test_watch_to_available(self, fake, fast_poll):
        mgr = Manager(fake, namespace="default", server_image="img:t")
        stop = threading.Event()
        kubelet = threading.Thread(target=play_kubelet, args=(fake, stop),
                                   daemon=True)
        kubelet.start()
        mgr.start(workers=2, serve_health=False)
        try:
            fake.create(model_obj("e2e"))
            _until(lambda: is_condition_true(
                fake.get(API_VERSION, KIND, "default", "e2e") or {},
                "Available"), "model never became Available")
            dep = fake.get("apps/v1", "Deployment", "default",
                           "ollama-model-e2e")
            assert dep is not None
            assert fake.get("v1", "Service", "default",
                            "ollama-model-e2e") is not None
        finally:
            stop.set()
            mgr.stop()

    def test_workload_drift_heals(self, fake, fast_poll):
        mgr = Manager(fake, namespace="default", server_image="img:t")
        stop = threading.Event()
        kubelet = threading.Thread(target=play_kubelet, args=(fake, stop),
                                   daemon=True)
        kubelet.start()
        mgr.start(workers=2, serve_health=False)
        try:
            fake.create(model_obj("drift"))
            _until(lambda: is_condition_true(
                fake.get(API_VERSION, KIND, "default", "drift") or {},
                "Available"), "model never became Available")
            # sabotage the deployment: wrong replica count
            dep = fake.get("apps/v1", "Deployment", "default",
                           "ollama-model-drift")
            dep["spec"]["replicas"] = 7
            fake.update(dep)  # owned-workload watch maps back to the Model
            _until(lambda: fake.get(
                "apps/v1", "Deployment", "default",
                "ollama-model-drift")["spec"]["replicas"] == 1,
                "the owned Deployment's drift was never healed")
        finally:
            stop.set()
            mgr.stop()


class TestWorkQueueProcessing:
    def test_no_concurrent_processing_of_same_key(self):
        q = WorkQueue()
        q.add(("ns", "a"))
        key = q.get(timeout=1)
        assert key == ("ns", "a")
        # event arrives while a worker holds the key: must NOT hand it to
        # a second worker — marked dirty instead
        q.add(("ns", "a"))
        assert q.get(timeout=0.1) is None
        q.done(key)  # dirty → immediate requeue
        assert q.get(timeout=1) == ("ns", "a")
        q.done(("ns", "a"))
        assert q.get(timeout=0.1) is None

    def test_done_with_requeue_after(self):
        q = WorkQueue()
        q.add(("ns", "a"))
        key = q.get(timeout=1)
        q.done(key, requeue_after=0.05)
        assert q.get(timeout=1) == ("ns", "a")


class TestServerImageOverride:
    def test_spec_server_image_wins(self, fake):
        from ollama_operator_tpu.operator.reconciler import ModelReconciler
        from ollama_operator_tpu.operator.recorder import NullRecorder
        rec = ModelReconciler(fake, NullRecorder(),
                              server_image="operator-default:1")
        obj = model_obj("pinned")
        obj["spec"]["serverImage"] = "user/runtime:pin"
        fake.create(obj)
        for _ in range(12):
            rec.reconcile("default", "pinned")
            for sts in fake.list("apps/v1", "StatefulSet", "default"):
                fake.set_status("apps/v1", "StatefulSet", "default",
                                sts["metadata"]["name"],
                                {"readyReplicas":
                                 sts["spec"].get("replicas", 1)})
            for svc in fake.list("v1", "Service", "default"):
                if not svc["spec"].get("clusterIP"):
                    svc["spec"]["clusterIP"] = "10.1.1.1"
                    fake.update(svc)
            dep = fake.get("apps/v1", "Deployment", "default",
                           "ollama-model-pinned")
            if dep:
                break
        tpl = dep["spec"]["template"]["spec"]
        assert tpl["containers"][0]["image"] == "user/runtime:pin"
        assert tpl["initContainers"][0]["image"] == "user/runtime:pin"
        # the shared store keeps the operator image (it serves all models)
        sts = fake.get("apps/v1", "StatefulSet", "default",
                       "ollama-models-store")
        assert sts["spec"]["template"]["spec"]["containers"][0][
            "image"] == "operator-default:1"


class TestMetricsAuth:
    """Bearer-token gate on /metrics (parity with the reference's
    kube-rbac-proxy guard, config/default/manager_auth_proxy_patch.yaml;
    here config/default/manager_metrics_auth_patch.yaml wires a Secret
    into METRICS_TOKEN_FILE and the manager enforces it natively)."""

    def _serve(self, fake, monkeypatch, **env):
        import urllib.request
        for k in ("METRICS_TOKEN_FILE", "METRICS_TOKEN"):
            monkeypatch.delenv(k, raising=False)
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        mgr = Manager(fake, namespace="default", server_image="img:t",
                      health_addr=("127.0.0.1", 0))
        httpd = mgr._health_server()
        port = httpd.server_address[1]

        def get(path, token=None):
            req = urllib.request.Request(f"http://127.0.0.1:{port}{path}")
            if token is not None:
                req.add_header("Authorization", f"Bearer {token}")
            try:
                return urllib.request.urlopen(req, timeout=10).status
            except urllib.error.HTTPError as e:
                return e.code

        return httpd, get

    def test_open_without_config(self, fake, monkeypatch):
        httpd, get = self._serve(fake, monkeypatch)
        try:
            assert get("/metrics") == 200
        finally:
            httpd.shutdown()

    def test_token_required_and_checked(self, fake, monkeypatch, tmp_path):
        tok = tmp_path / "token"
        tok.write_text("s3cret\n")
        httpd, get = self._serve(fake, monkeypatch,
                                 METRICS_TOKEN_FILE=str(tok))
        try:
            assert get("/metrics") == 401
            assert get("/metrics", token="wrong") == 401
            assert get("/metrics", token="s3cret") == 200
            assert get("/healthz") == 200          # probes stay open
        finally:
            httpd.shutdown()

    def test_missing_token_file_fails_closed(self, fake, monkeypatch,
                                             tmp_path):
        httpd, get = self._serve(
            fake, monkeypatch,
            METRICS_TOKEN_FILE=str(tmp_path / "absent"))
        try:
            assert get("/metrics") == 401
            assert get("/metrics", token="") == 401
            assert get("/healthz") == 200
        finally:
            httpd.shutdown()


class TestPollBackoff:
    def test_poll_requeues_back_off_per_model(self, fake):
        """A Model stuck at steady-state POLL backs off 5 → 7.5 → …
        capped at 60s; any shorter (progress) requeue resets its streak;
        other models are unaffected."""
        from ollama_operator_tpu.operator.reconciler import Result
        mgr = Manager(fake, namespace="default", server_image="img:t")
        seen = {}
        done_evt = threading.Event()
        real_done = mgr.queue.done

        def spy_done(key, requeue_after=-1.0):
            seen.setdefault(key, []).append(requeue_after)
            real_done(key)           # finish WITHOUT the real delay
            done_evt.set()

        mgr.queue.done = spy_done
        scripts = {"stuck": iter([5.0] * 9),
                   "moving": iter([5.0, 5.0, 0.5, 5.0])}

        class StubRec:
            def reconcile(self, ns, name):
                return Result(requeue_after=next(scripts[name]))

        mgr.reconciler = StubRec()
        t = threading.Thread(target=mgr._worker, daemon=True)
        t.start()
        try:
            for name, n in (("stuck", 9), ("moving", 4)):
                for _ in range(n):
                    done_evt.clear()
                    mgr.queue.add(("default", name))
                    assert done_evt.wait(5)
        finally:
            mgr._stop.set()
            mgr.queue.shutdown()
            t.join(timeout=5)
        stuck = seen[("default", "stuck")]
        assert stuck[:4] == [5.0, 7.5, 11.25, 16.875]
        assert stuck[-2:] == [60.0, 60.0]          # capped, stays capped
        # progress (requeue < floor) resets the streak; next POLL starts over
        assert seen[("default", "moving")] == [5.0, 7.5, 0.5, 5.0]
