"""Invariant linter (tools/invariant_lint).

Each pass is proven against a miniature fixture tree under
tests/fixtures/lint/ with seeded violations — exact finding counts,
messages, and suppression behavior — and the shipped tree itself must
lint clean (zero unsuppressed findings), which is the CI gate's
contract.
"""

import json
from pathlib import Path

import pytest

from tools.invariant_lint import ALL_PASSES, LintConfig, run_passes
from tools.invariant_lint.core import (render_github, render_json,
                                       render_summary_markdown, summarize)
from tools.invariant_lint.passes import (DeterminismPass,
                                         ExceptionHygienePass,
                                         FaultCatalogPass,
                                         FollowerPurityPass, HostSyncPass,
                                         KnobRegistryPass, LockOrderPass,
                                         MetricsDisciplinePass,
                                         TraceEnvPass)

REPO = Path(__file__).resolve().parents[1]
FIX = REPO / "tests" / "fixtures" / "lint"


def fixture_config(case, **overrides):
    defaults = dict(
        root=FIX / case,
        code_roots=("pkg",),
        knobs_module="pkg/knobs.py",
        docs_roots=("docs/en", "docs/zh"),
        metrics_module="pkg/metrics.py",
        hot_roots=(("pkg/engine.py", "decode_n_launch"),),
        graph_scopes=("pkg",),
        follower_module="pkg/follower.py",
        determinism_modules=("pkg/engine.py",),
        exception_scopes=("pkg",),
        faults_module="pkg/faults.py",
        trace_env_scopes=("pkg/ops", "pkg/models"),
        trace_env_resolvers=(("pkg/ops/attention.py",
                              "_kernels_override"),),
    )
    defaults.update(overrides)
    return LintConfig(**defaults)


def run_one(case, pass_obj, **overrides):
    cfg = fixture_config(case, **overrides)
    return run_passes(cfg, [pass_obj])


def unsuppressed(findings):
    return [f for f in findings if not f.suppressed]


# -- knob-registry ----------------------------------------------------------

def test_knob_registry_fixture():
    fs = run_one("knobs", KnobRegistryPass())
    live = unsuppressed(fs)
    msgs = [f.message for f in live]
    assert len(live) == 6, msgs
    assert sum("TPU_FIX_B is read here but not declared" in m
               for m in msgs) == 1
    assert sum("TPU_FIX_STALE is declared but no code mentions" in m
               for m in msgs) == 1
    assert sum("missing from the docs/en knob tables" in m
               for m in msgs) == 1          # TPU_FIX_STALE only
    assert sum("missing from the docs/zh knob tables" in m
               for m in msgs) == 2          # TPU_FIX_A + TPU_FIX_STALE
    assert sum("docs mention TPU_FIX_GHOST" in m for m in msgs) == 1
    # the suppressed undeclared read carries its reason
    supp = [f for f in fs if f.suppressed]
    assert len(supp) == 1
    assert supp[0].suppress_reason == "fixture exercises suppression"
    assert "TPU_FIX_SUPP" in supp[0].message


def test_knob_registry_read_sites_are_finding_anchors():
    fs = unsuppressed(run_one("knobs", KnobRegistryPass()))
    read = [f for f in fs if "TPU_FIX_B" in f.message][0]
    assert read.path == "pkg/mod.py"
    assert read.line == 8


# -- trace-env --------------------------------------------------------------

def test_trace_env_fixture_flags_reads_in_traced_packages():
    """A ``TPU_*`` read under ops/ chooses a kernel at trace time, where
    it is no cache key: each read is a finding, whatever its spelling
    (``os.environ.get``, a bare-imported ``getenv``, a subscript), in both
    scoped packages, and a suppression needs its reason."""
    fs = run_one("traceenv", TraceEnvPass())
    live = unsuppressed(fs)
    assert [(f.path, f.line) for f in live] == [
        ("pkg/models/decoder.py", 8),     # a namesake is not the resolver
        ("pkg/ops/paged.py", 9),          # os.environ.get("TPU_FIX_KERNEL")
        ("pkg/ops/paged.py", 11),         # a bare-imported getenv(...)
    ]
    assert all("not a jit cache key" in f.message for f in live)
    supp = [f for f in fs if f.suppressed]
    assert [(f.path, f.suppress_reason) for f in supp] == [
        ("pkg/ops/paged.py", "fixture exercises suppression")]


def test_trace_env_passes_the_resolver_and_code_outside_the_scope():
    """The one sanctioned read (the resolver of OLLAMA_TPU_KERNELS) and
    reads outside ops/ and models/ are not this pass's findings; in the
    shipped tree the resolver exists, is the only read there, and is
    what the rule names."""
    fs = run_one("traceenv", TraceEnvPass())
    assert not [f for f in fs if f.path in ("pkg/ops/attention.py",
                                            "pkg/runtime/engine.py")]
    cfg = LintConfig(root=REPO)
    assert not run_passes(cfg, [TraceEnvPass()])
    (mod, fn), = cfg.trace_env_resolvers
    src = (REPO / mod).read_text()
    assert f"def {fn}(" in src and src.count("os.environ") == 1
    # with no resolver sanctioned, that one read is what is left
    bare = run_passes(LintConfig(root=REPO, trace_env_resolvers=()),
                      [TraceEnvPass()])
    assert [f.path for f in bare] == [mod]


# -- metrics-discipline -----------------------------------------------------

def test_metrics_discipline_fixture():
    fs = unsuppressed(run_one("metrics", MetricsDisciplinePass()))
    msgs = [f.message for f in fs]
    assert len(fs) == 3, msgs
    assert sum("tpu_model_fix_missing_total is used but never described"
               in m for m in msgs) == 1
    assert sum("tpu_model_fix_missing_total is incremented but never "
               "pre-seeded" in m for m in msgs) == 1
    assert sum("label keys {other}" in m for m in msgs) == 1
    # both seed idioms (batch loop + literal combos) satisfied the rest
    assert not any("fix_ok_total" in m for m in msgs)


# -- host-sync-hot-path -----------------------------------------------------

def test_host_sync_fixture():
    fs = run_one("hotsync", HostSyncPass())
    live = unsuppressed(fs)
    msgs = [f.message for f in live]
    assert len(live) == 3, msgs
    assert sum(".item()" in m for m in msgs) == 1
    assert sum("np.asarray" in m for m in msgs) == 1
    assert sum("int(x[...])" in m for m in msgs) == 1
    # every live finding sits in the reachable helper, none in cold()
    assert all("_helper" in m for m in msgs)
    supp = [f for f in fs if f.suppressed]
    assert len(supp) == 1 and "block_until_ready" in supp[0].message


# -- lock-order -------------------------------------------------------------

def test_lock_order_fixture():
    fs = unsuppressed(run_one("lockorder", LockOrderPass()))
    msgs = [f.message for f in fs]
    cycle = [m for m in msgs if "lock-order cycle" in m]
    blocking = [m for m in msgs if "while holding" in m
                and "cycle" not in m]
    assert len(cycle) == 2, msgs          # A->B and B->A edges
    assert any("A._la" in m and "B._lb" in m for m in cycle)
    assert len(blocking) == 2, msgs
    assert sum("time.sleep" in m for m in blocking) == 1
    assert sum("socket sendall (via A._push)" in m
               for m in blocking) == 1
    # the RLock re-entry produced nothing
    assert not any("R._lr" in m for m in msgs)


# -- follower-purity --------------------------------------------------------

def test_follower_purity_fixture():
    fs = unsuppressed(run_one("follower", FollowerPurityPass()))
    assert len(fs) == 1, [f.message for f in fs]
    f = fs[0]
    assert "FLIGHT" in f.message
    assert f.path == "pkg/follower.py"
    # flagged in the helper the handler reaches, not in unrelated()
    assert f.line == 13


# -- determinism ------------------------------------------------------------

def test_determinism_fixture():
    fs = unsuppressed(run_one("determinism", DeterminismPass()))
    msgs = [f.message for f in fs]
    assert len(fs) == 4, msgs
    assert sum("time.time()" in m for m in msgs) == 1
    assert sum("random.random" in m for m in msgs) == 1
    assert sum("a set literal" in m for m in msgs) == 1
    assert sum("the set 'PAGES'" in m for m in msgs) == 1


# -- exception-hygiene ------------------------------------------------------

def test_exception_hygiene_fixture():
    fs = run_one("exceptions", ExceptionHygienePass())
    live = unsuppressed(fs)
    by_pass = {}
    for f in live:
        by_pass.setdefault(f.pass_id, []).append(f)
    assert len(by_pass.get("exception-hygiene", [])) == 2   # bare + swallow
    # the reasonless allow() is itself a finding
    assert len(by_pass.get("suppression", [])) == 1
    assert "no reason string" in by_pass["suppression"][0].message
    supp = [f for f in fs if f.suppressed]
    assert len(supp) == 2
    reasons = {f.suppress_reason for f in supp}
    assert "fixture-justified teardown" in reasons
    assert None in reasons                                  # the reasonless one


# -- fault-catalog ----------------------------------------------------------

def test_fault_catalog_fixture():
    fs = run_one("faults", FaultCatalogPass())
    live = unsuppressed(fs)
    msgs = [f.message for f in live]
    assert len(live) == 4, msgs
    assert sum('"fix.ghost" is checked here but not registered' in m
               for m in msgs) == 1
    assert sum("computed point name" in m for m in msgs) == 1
    assert sum('"fix.stale" is registered but no' in m for m in msgs) == 1
    assert sum('"fix.nodoc" is registered but missing from the docs/zh'
               in m for m in msgs) == 1
    # healthy point produced nothing; suppression carries its reason
    assert not any("fix.ok" in m for m in msgs)
    supp = [f for f in fs if f.suppressed]
    assert len(supp) == 1
    assert supp[0].suppress_reason == "fixture exercises suppression"
    assert "fix.tolerated" in supp[0].message


def test_fault_catalog_finding_anchors():
    fs = unsuppressed(run_one("faults", FaultCatalogPass()))
    ghost = [f for f in fs if "fix.ghost" in f.message][0]
    assert ghost.path == "pkg/mod.py"
    stale = [f for f in fs if "fix.stale" in f.message][0]
    assert stale.path == "pkg/faults.py"


# -- output formats ---------------------------------------------------------

def test_json_schema_and_renderers():
    fs = run_one("exceptions", ExceptionHygienePass())
    doc = json.loads(render_json(ALL_PASSES, fs))
    assert doc["version"] == 1
    assert {r["id"] for r in doc["passes"]} == (
        {p.id for p in ALL_PASSES} | {"suppression", "parse"})
    for f in doc["findings"]:
        assert set(f) == {"path", "line", "pass", "severity", "message",
                          "suppressed", "suppress_reason"}
        assert isinstance(f["line"], int) and f["line"] >= 1
    gh = render_github(fs)
    assert "::error file=pkg/mod.py,line=" in gh
    assert "title=invariant-lint [exception-hygiene]" in gh
    # suppressed findings never become annotations
    assert gh.count("::error") == len(unsuppressed(fs))
    md = render_summary_markdown(ALL_PASSES, fs)
    assert "| `exception-hygiene` |" in md and "gate fails" in md


def test_pass_ids_unique_and_kebab():
    ids = [p.id for p in ALL_PASSES]
    assert len(ids) == len(set(ids)) == 9
    for pid in ids:
        assert pid == pid.lower() and " " not in pid


# -- the shipped tree is the contract ---------------------------------------

def test_shipped_tree_has_zero_unsuppressed_findings():
    fs = run_passes(LintConfig(root=REPO), ALL_PASSES)
    live = unsuppressed(fs)
    assert not live, "\n".join(f.render() for f in live)
    # every suppression in the tree carries a justification
    assert all(f.suppress_reason for f in fs if f.suppressed)


def test_shipped_tree_exercises_every_suppressible_pass():
    """The suppression policy is load-bearing: the tree documents its
    intentional violations rather than hiding them, so the passes that
    have known-intentional sites must show suppressed findings."""
    fs = run_passes(LintConfig(root=REPO), ALL_PASSES)
    rows = {r["id"]: r for r in summarize(ALL_PASSES, fs)}
    for pid in ("host-sync-hot-path", "lock-order", "follower-purity",
                "exception-hygiene"):
        assert rows[pid]["suppressed"] > 0, pid
        assert rows[pid]["findings"] == 0, pid


def test_every_tpu_knob_read_is_declared_and_documented():
    """Acceptance: 100% of TPU_* env reads declared in runtime/knobs.py
    and present in both docs trees (the knob-registry pass emits nothing
    at all on the shipped tree)."""
    fs = run_passes(LintConfig(root=REPO), [KnobRegistryPass()])
    assert not fs, "\n".join(f.render() for f in fs)


def test_every_fault_check_site_is_catalogued_and_documented():
    """Acceptance: every FAULTS.check site in the shipped tree names a
    registered catalog point, and both docs trees' fault-point tables
    list every point — so the chaos campaign's `FAULTS.points()` draw
    really covers every recovery path in the code."""
    fs = run_passes(LintConfig(root=REPO), [FaultCatalogPass()])
    assert not fs, "\n".join(f.render() for f in fs)
    from ollama_operator_tpu.runtime.faults import CATALOG, FAULTS
    assert [p.name for p in FAULTS.points()] == sorted(CATALOG)
    assert len(CATALOG) >= 12


def test_registry_importable_and_nonempty():
    from ollama_operator_tpu.runtime import knobs
    assert len(knobs.REGISTRY) >= 80
    k = knobs.lookup("TPU_DECODE_CHUNK")
    assert k is not None and k.subsystem == "engine"
    with pytest.raises(ValueError):
        knobs.declare("TPU_DECODE_CHUNK", "int", 0, "engine", "dup")
    assert [x.name for x in knobs.all_knobs()] == sorted(knobs.REGISTRY)
