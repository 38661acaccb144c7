"""Workload-builder unit tests (pkg/model's pure functions, SURVEY.md §4a).

Covers the reference's object shapes — store trio names/sizes/mounts
(image_store.go), per-model deployment with puller init container and RO
mount (model.go, pod.go) — plus the TPU additions (resources, selectors,
multi-host env) and the deliberately fixed reference gaps
(imagePullPolicy/Secrets honored).
"""

import pytest

from ollama_operator_tpu.operator import pod as podf
from ollama_operator_tpu.operator import workload
from ollama_operator_tpu.operator.types import ModelSpecView


def model_obj(name="phi", namespace="default", **spec):
    spec.setdefault("image", "phi")
    return {
        "apiVersion": "ollama.ayaka.io/v1",
        "kind": "Model",
        "metadata": {"name": name, "namespace": namespace, "uid": "u1"},
        "spec": spec,
    }


class TestImageStore:
    def test_pvc_defaults(self):
        pvc = workload.build_store_pvc("ns1", ModelSpecView(model_obj()))
        assert pvc["metadata"]["name"] == "ollama-models-store-pvc"
        assert pvc["spec"]["accessModes"] == ["ReadWriteMany"]
        assert pvc["spec"]["resources"]["requests"]["storage"] == "100Gi"
        assert "storageClassName" not in pvc["spec"]

    def test_pvc_spec_overrides(self):
        m = model_obj(storageClassName="fast",
                      persistentVolume={"accessMode": "ReadWriteOnce"})
        pvc = workload.build_store_pvc("ns1", ModelSpecView(m))
        assert pvc["spec"]["storageClassName"] == "fast"
        assert pvc["spec"]["accessModes"] == ["ReadWriteOnce"]

    def test_store_statefulset_mounts_rw(self):
        sts = workload.build_store_statefulset(
            "ns1", ModelSpecView(model_obj()), "img:1")
        tpl = sts["spec"]["template"]["spec"]
        c = tpl["containers"][0]
        assert sts["spec"]["serviceName"] == "ollama-models-store"
        assert c["volumeMounts"][0]["readOnly"] is False
        assert {"name": "TPU_STORE_ONLY", "value": "1"} in c["env"]
        assert tpl["volumes"][0]["persistentVolumeClaim"]["claimName"] == \
            "ollama-models-store-pvc"

    def test_store_service(self):
        svc = workload.build_store_service("ns1")
        assert svc["spec"]["selector"] == {"app": "ollama-models-store"}
        assert svc["spec"]["ports"][0]["port"] == 11434


class TestModelDeployment:
    def test_basic_shape(self):
        dep = workload.build_model_deployment(model_obj(runtime="cpu"))
        assert dep["metadata"]["name"] == "ollama-model-phi"
        assert dep["spec"]["replicas"] == 1
        assert dep["spec"]["selector"]["matchLabels"] == \
            {"app": "ollama-model-phi"}
        owner = dep["metadata"]["ownerReferences"][0]
        assert owner["kind"] == "Model" and owner["uid"] == "u1"
        tpl = dep["spec"]["template"]["spec"]
        assert "nodeSelector" not in tpl  # cpu runtime: no TPU selectors
        init = tpl["initContainers"][0]
        assert init["args"] == ["pull", "phi"]
        assert init["env"][0]["value"] == "ollama-models-store.default"
        server = tpl["containers"][0]
        # blob mount RO + RW cache subPath mount layered on top
        assert server["volumeMounts"][0]["readOnly"] is True
        assert server["volumeMounts"][1]["subPath"] == "tpu-cache"
        assert server["volumeMounts"][1]["readOnly"] is False
        assert server["readinessProbe"]["httpGet"]["path"] == "/api/tags"
        assert server["readinessProbe"]["failureThreshold"] == 2500

    def test_replicas_and_pull_options_honored(self):
        m = model_obj(replicas=3, imagePullPolicy="Never",
                      imagePullSecrets=[{"name": "reg-cred"}], runtime="cpu")
        dep = workload.build_model_deployment(m)
        assert dep["spec"]["replicas"] == 3
        tpl = dep["spec"]["template"]["spec"]
        assert tpl["imagePullSecrets"] == [{"name": "reg-cred"}]
        assert tpl["containers"][0]["imagePullPolicy"] == "Never"
        assert tpl["initContainers"][0]["imagePullPolicy"] == "Never"

    def test_tpu_single_host(self):
        m = model_obj(tpu={"topology": "v5e-4"}, contextLength=8192,
                      quantization="int8", sharding={"tp": 4})
        dep = workload.build_model_deployment(m)
        tpl = dep["spec"]["template"]["spec"]
        assert tpl["nodeSelector"]["cloud.google.com/gke-tpu-topology"] == \
            "2x2"
        assert tpl["tolerations"][0]["key"] == "google.com/tpu"
        server = tpl["containers"][0]
        assert server["resources"]["limits"]["google.com/tpu"] == "4"
        env = {e["name"]: e.get("value") for e in server["env"]}
        assert env["TPU_MAX_SEQ_LEN"] == "8192"
        assert env["TPU_ENGINE_DTYPE"] == "int8"
        assert env["TPU_KV_DTYPE"] == "int8"
        assert env["TPU_EXPECT_PLATFORM"] == "tpu"
        assert env["TPU_TENSOR_PARALLEL"] == "4"
        assert env["TPU_PRELOAD_MODEL"] == "phi"
        # the compile cache is placed from outside, beside the weights
        assert env["JAX_COMPILATION_CACHE_DIR"] == \
            env["TPU_WEIGHT_CACHE"] + "/xla-cache"

    def test_external_pvc_used_without_creating(self):
        m = model_obj(runtime="cpu",
                      persistentVolumeClaim={"claimName": "my-claim"})
        dep = workload.build_model_deployment(m)
        vol = dep["spec"]["template"]["spec"]["volumes"][0]
        assert vol["persistentVolumeClaim"]["claimName"] == "my-claim"


class TestMultiHost:
    def test_statefulset_shape(self):
        m = model_obj(name="llama70b", image="llama2:70b",
                      tpu={"topology": "v5e-16"})
        sts = workload.build_model_statefulset(m)
        assert sts["spec"]["replicas"] == 4  # 4 hosts × 4 chips
        assert sts["spec"]["podManagementPolicy"] == "Parallel"
        assert sts["spec"]["serviceName"] == "ollama-model-llama70b-hosts"
        tpl = sts["spec"]["template"]["spec"]
        env = {e["name"]: e.get("value")
               for e in tpl["containers"][0]["env"] if "value" in e}
        assert env["TPU_DIST_HOSTS"] == "4"
        assert env["TPU_DIST_CHIPS_PER_HOST"] == "4"
        assert "ollama-model-llama70b-hosts.default.svc:8476" in \
            env["TPU_DIST_COORDINATOR"]
        assert tpl["nodeSelector"]["cloud.google.com/gke-tpu-topology"] == \
            "4x4"

    def test_headless_service(self):
        m = model_obj(name="llama70b", tpu={"topology": "v5e-16"})
        svc = workload.build_headless_service(m)
        assert svc["spec"]["clusterIP"] == "None"
        assert svc["spec"]["publishNotReadyAddresses"] is True

    def test_serving_service_targets_host0(self):
        m = model_obj(name="llama70b", tpu={"topology": "v5e-16"})
        svc = workload.build_model_service(m)
        assert svc["spec"]["selector"][
            "apps.kubernetes.io/pod-index"] == "0"

    def test_single_host_service_has_no_index_selector(self):
        svc = workload.build_model_service(model_obj(runtime="cpu"))
        assert "apps.kubernetes.io/pod-index" not in svc["spec"]["selector"]


class TestSpecView:
    def test_defaults(self):
        v = ModelSpecView(model_obj())
        assert v.replicas == 1 and v.runtime == "tpu"
        assert v.tpu_placement().topology == "v5e-1"

    def test_unknown_topology_rejected(self):
        v = ModelSpecView(model_obj(tpu={"topology": "v9-999"}))
        with pytest.raises(ValueError, match="unknown tpu.topology"):
            v.tpu_placement()

    def test_cpu_runtime_no_placement(self):
        assert ModelSpecView(model_obj(runtime="cpu")).tpu_placement() is None


class TestDriftDetection:
    """update_model_workload must not see apiserver defaulting as drift
    (a real apiserver enriches live pod templates with defaulted fields),
    but must catch real template changes via the spec-hash annotation."""

    def _mk(self):
        from ollama_operator_tpu.operator.recorder import NullRecorder
        from fake_kube import FakeKube
        kube = FakeKube()
        m = model_obj(runtime="cpu")
        want = workload.build_model_deployment(m, "img:1")
        workload.stamp_spec_hash(want)
        kube.create(want)
        return kube, m, want

    def test_apiserver_defaulting_is_not_drift(self):
        from ollama_operator_tpu.operator.recorder import NullRecorder
        kube, m, want = self._mk()
        cur = kube.get("apps/v1", "Deployment", "default", "ollama-model-phi")
        # simulate apiserver defaulting on the live object
        tpl = cur["spec"]["template"]["spec"]
        tpl["dnsPolicy"] = "ClusterFirst"
        for c in tpl["containers"]:
            c["terminationMessagePath"] = "/dev/termination-log"
            c.setdefault("resources", {})
        kube.update(cur)
        cur = kube.get("apps/v1", "Deployment", "default", "ollama-model-phi")
        rec = NullRecorder()
        assert workload.update_model_workload(kube, rec, m, cur, want) is False
        assert rec._events == []

    def test_real_template_change_is_drift(self):
        from ollama_operator_tpu.operator.recorder import NullRecorder
        kube, m, _ = self._mk()
        m2 = model_obj(runtime="cpu", image="phi:v2")
        want2 = workload.build_model_deployment(m2, "img:1")
        workload.stamp_spec_hash(want2)
        cur = kube.get("apps/v1", "Deployment", "default", "ollama-model-phi")
        assert workload.update_model_workload(
            kube, NullRecorder(), m2, cur, want2) is True
        cur = kube.get("apps/v1", "Deployment", "default", "ollama-model-phi")
        assert cur["spec"]["template"]["spec"]["initContainers"][0][
            "args"] == ["pull", "phi:v2"]
        assert cur["metadata"]["annotations"][
            workload.SPEC_HASH_ANNOTATION] == workload.spec_hash(want2)


class TestProbes:
    def test_liveness_fails_fast_startup_tolerates_load(self):
        dep = workload.build_model_deployment(model_obj(runtime="cpu"))
        server = dep["spec"]["template"]["spec"]["containers"][0]
        assert server["startupProbe"]["failureThreshold"] == 2500
        assert server["livenessProbe"]["failureThreshold"] == 3
        assert server["livenessProbe"]["httpGet"]["path"] == "/livez"
