"""Scale-to-zero warm restore: engine warm-state snapshot/restore and the
image-store PVC persistence (gguf/store.py warm_snapshot helpers).

The contract under test is the wake path's recompile budget: a replica
cold-started from a warm snapshot must register the full warm plan and
serve its first streams with `tpu_model_recompiles_total` untouched —
byte-identical to a replica that ran the full warm_buckets() pass.

The serialized-executable payload path (TPU_WARM_SNAPSHOT_EXECS) is
deliberately disabled here — and is off by default on the CPU backend
(Engine._snapshot_execs_ok): this host's CPU-backend executable
deserialization is unstable (see conftest.py's note on the persistent
compilation cache), and the payloads are best-effort by design — a
snapshot of signatures alone must already deliver the zero-recompile
wake, just with compile time instead of deserialize time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pickle
import pytest

from ollama_operator_tpu.gguf.store import (load_warm_snapshot,
                                            save_warm_snapshot,
                                            warm_snapshot_path)
from ollama_operator_tpu.models import config as cfglib
from ollama_operator_tpu.models import decoder
from ollama_operator_tpu.runtime.engine import (Engine, EngineConfig,
                                                SlotOptions)
from ollama_operator_tpu.server.metrics import GLOBAL as METRICS

rng = np.random.default_rng(47)

# two prefill buckets (16, 32) keep the per-test compile bill small; the
# snapshot/restore logic is bucket-count-independent
ECFG = EngineConfig(max_slots=2, max_seq_len=32, min_prefill_bucket=16,
                    cache_dtype=jnp.float32, decode_chunk=4)


def tiny(**kw):
    base = cfglib.PRESETS["tiny"]
    return cfglib.ModelConfig(**{**base.__dict__, **kw}).validate()


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params = decoder.init_params(cfg, jax.random.PRNGKey(5),
                                 dtype=jnp.float32)
    return cfg, params


@pytest.fixture(scope="module")
def donor_blob(model):
    """One fully-warmed donor engine, snapshotted; compile passes are the
    whole cost of this module, so every test shares this snapshot."""
    cfg, params = model
    donor = Engine(cfg, params, ecfg=ECFG)
    donor.warm_buckets()
    assert donor._warmed_sigs
    blob = donor.warm_snapshot()
    return set(donor._warmed_sigs), blob


@pytest.fixture(autouse=True)
def _sigs_only(monkeypatch):
    monkeypatch.setenv("TPU_WARM_SNAPSHOT_EXECS", "0")


def _recompile_total():
    return sum(METRICS.get("tpu_model_recompiles_total", f'{{kind="{k}"}}')
               for k in ("decode", "admit", "admit_many", "extend"))


class TestEngineSnapshot:
    def test_warm_restored_engine_serves_without_recompiles(
            self, model, donor_blob):
        """Acceptance: cold start from snapshot, then dispatch — the
        recompile counter delta stays 0 vs > 0 for the no-snapshot
        control arm, and the decoded tokens are identical."""
        cfg, params = model
        sigs, blob = donor_blob
        prompt = np.asarray(rng.integers(1, cfg.vocab_size, 11), np.int32)
        opts = SlotOptions(temperature=0.0)

        warmed = Engine(cfg, params, ecfg=ECFG)
        out = warmed.restore_warm(blob)
        assert out["restored"] + out["compiled"] == len(sigs)
        assert warmed._warmed_sigs == sigs
        # the restore itself counted zero recompiles...
        assert all(v == 0 for v in warmed.recompiles.values())
        total0 = _recompile_total()
        t_warm = warmed.admit(0, prompt, opts)
        warm_toks = [np.asarray(warmed.decode_n()) for _ in range(3)]
        # ...and so did the first post-wake dispatches
        assert _recompile_total() == total0          # zero-recompile wake
        assert all(v == 0 for v in warmed.recompiles.values())

        control = Engine(cfg, params, ecfg=ECFG)     # no snapshot
        t_ctl = control.admit(0, prompt, opts)
        ctl_toks = [np.asarray(control.decode_n()) for _ in range(3)]
        assert _recompile_total() > total0           # control recompiles
        assert sum(control.recompiles.values()) > 0

        assert t_warm == t_ctl
        for a, b in zip(warm_toks, ctl_toks):
            np.testing.assert_array_equal(a, b)

    def test_version_and_backend_mismatch_falls_back_to_recompile(
            self, model, donor_blob):
        cfg, params = model
        _, blob = donor_blob
        snap = pickle.loads(blob)
        snap["jax"] = "0.0.0"                  # incompatible provenance
        snap["sigs"] = snap["sigs"][:2]        # keep the compile bill tiny
        snap["execs"] = {}
        eng = Engine(cfg, params, ecfg=ECFG)
        out = eng.restore_warm(pickle.dumps(snap))
        assert out["restored"] == 0
        assert out["compiled"] == 2
        assert len(eng._warmed_sigs) == 2
        assert all(v == 0 for v in eng.recompiles.values())

    def test_a_kind_this_build_no_longer_has_is_skipped(
            self, model, donor_blob, monkeypatch):
        """A snapshot saved by a build that still warmed speculative
        verify programs lists ("spec", (k, bucket)) entries, payloads
        included: the restore skips them and warms the rest."""
        cfg, params = model
        _, blob = donor_blob
        snap = pickle.loads(blob)
        snap["sigs"] = snap["sigs"][:2] + [("spec", (3, 16))]
        snap["execs"] = {("spec", (3, 16)): (b"gone", pickle.dumps((0, 0)))}
        eng = Engine(cfg, params, ecfg=ECFG)
        assert not eng._install_exec(("spec", (3, 16)), object())
        out = eng.restore_warm(pickle.dumps(snap))
        assert out == {"restored": 0, "compiled": 2}
        assert {s[0] for s in eng._warmed_sigs} <= {"decode", "admit",
                                                    "admit_many", "extend"}
        assert all(v == 0 for v in eng.recompiles.values())

    def test_unknown_snapshot_version_rejected(self, model):
        cfg, params = model
        eng = Engine(cfg, params, ecfg=ECFG)
        with pytest.raises(ValueError):
            eng.restore_warm(pickle.dumps({"version": 99}))


def test_exec_payloads_are_accelerator_only_by_default(monkeypatch):
    """Unset TPU_WARM_SNAPSHOT_EXECS must NOT ship executable payloads
    on the CPU backend (deserialization there is unstable on some hosts
    — the original default-on corrupted a reloading server): a CPU
    snapshot carries signatures only, and a CPU restore ignores any
    exec payloads a blob does carry.  "1" forces the path back on."""
    monkeypatch.delenv("TPU_WARM_SNAPSHOT_EXECS", raising=False)
    assert jax.default_backend() == "cpu"
    assert Engine._snapshot_execs_ok() is False
    monkeypatch.setenv("TPU_WARM_SNAPSHOT_EXECS", "1")
    assert Engine._snapshot_execs_ok() is True
    monkeypatch.setenv("TPU_WARM_SNAPSHOT_EXECS", "0")
    assert Engine._snapshot_execs_ok() is False


class TestSnapshotStore:
    def test_roundtrip(self, tmp_path):
        blob = b"\x00warm\xff" * 100
        path = save_warm_snapshot(str(tmp_path), "abc123", blob)
        assert path == warm_snapshot_path(str(tmp_path), "abc123")
        assert load_warm_snapshot(str(tmp_path), "abc123") == blob
        # last-finisher-wins overwrite, reader never sees a torn file
        save_warm_snapshot(str(tmp_path), "abc123", b"v2")
        assert load_warm_snapshot(str(tmp_path), "abc123") == b"v2"

    def test_missing_is_none(self, tmp_path):
        assert load_warm_snapshot(str(tmp_path), "nope") is None
