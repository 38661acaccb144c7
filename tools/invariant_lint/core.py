"""Invariant-linter core: file walker, finding model, suppressions, runner.

Eleven PRs of runtime invariants — pre-seeded metric families, host-only
flight-recorder events, replay determinism, a host-sync-free dispatch hot
path — were enforced only by runtime spot checks and reviewer memory.
This package makes them diff-time checks: a pluggable set of AST passes
over the tree (stdlib ``ast`` only, zero dependencies, same philosophy as
runtime/trace.py), each producing findings that must be fixed or
explicitly suppressed inline::

    # lint: allow(<pass-id>): <reason>

A suppression covers findings of that pass on the same line or the line
directly below the comment (so it can sit above a multi-line construct).
A suppression without a reason string is itself a finding — the whole
point is that every intentional violation carries its justification in
the tree.

The pass catalog lives in :mod:`tools.invariant_lint.passes`; project
geometry (which files are hot-path roots, where the knob registry lives)
is a :class:`LintConfig`, so the test-suite fixtures can lint miniature
trees with the exact same machinery.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

SUPPRESS_RE = re.compile(
    r"#\s*lint:\s*allow\(([a-z0-9_-]+)\)(?::\s*(.*?))?\s*(?:#|$)")

SEVERITIES = ("error", "warning")


@dataclasses.dataclass
class Finding:
    """One invariant violation at a source location."""

    path: str           # repo-relative, posix separators
    line: int           # 1-based
    pass_id: str
    message: str
    severity: str = "error"
    suppressed: bool = False
    suppress_reason: Optional[str] = None

    def to_dict(self) -> dict:
        return {
            "path": self.path,
            "line": self.line,
            "pass": self.pass_id,
            "severity": self.severity,
            "message": self.message,
            "suppressed": self.suppressed,
            "suppress_reason": self.suppress_reason,
        }

    def render(self) -> str:
        tag = " (suppressed)" if self.suppressed else ""
        return (f"{self.path}:{self.line}: [{self.pass_id}] "
                f"{self.severity}: {self.message}{tag}")


class Source:
    """A parsed Python file plus its inline suppressions."""

    def __init__(self, path: Path, rel: str, text: str):
        self.path = path
        self.rel = rel
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=rel)
        # line -> {pass_id: reason or None}; a comment suppresses findings
        # on its own line and on the line directly below it
        self.suppressions: Dict[int, Dict[str, Optional[str]]] = {}
        for i, ln in enumerate(self.lines, start=1):
            if "lint:" not in ln:
                continue
            for m in SUPPRESS_RE.finditer(ln):
                reason = (m.group(2) or "").strip() or None
                self.suppressions.setdefault(i, {})[m.group(1)] = reason

    def suppression_for(self, pass_id: str,
                        line: int) -> Tuple[bool, Optional[str]]:
        for at in (line, line - 1):
            entry = self.suppressions.get(at)
            if entry and pass_id in entry:
                return True, entry[pass_id]
        return False, None


@dataclasses.dataclass
class LintConfig:
    """Project geometry the passes need.  Paths are repo-relative."""

    root: Path
    # directories (or single files) walked for Python sources
    code_roots: Tuple[str, ...] = ("ollama_operator_tpu",)
    # knob registry + the docs trees whose knob tables must list every
    # declared knob
    knobs_module: str = "ollama_operator_tpu/runtime/knobs.py"
    docs_roots: Tuple[str, ...] = ("docs/en", "docs/zh-CN")
    knob_prefix: str = "TPU_"
    # trace-env: packages jax.jit traces, and the (module, function)
    # pairs that alone may read the environment there
    trace_env_scopes: Tuple[str, ...] = ("ollama_operator_tpu/ops",
                                         "ollama_operator_tpu/models")
    trace_env_resolvers: Tuple[Tuple[str, str], ...] = (
        ("ollama_operator_tpu/ops/attention.py", "_kernels_override"),)
    # metric registry module holding describe() + pre-seed calls
    metrics_module: str = "ollama_operator_tpu/server/metrics.py"
    metric_prefix: str = "tpu_model_"
    # fault-point catalog module: every FAULTS.check() site must name a
    # point registered here, and the docs fault-point tables must list
    # every registered point
    faults_module: str = "ollama_operator_tpu/runtime/faults.py"
    # host-sync pass: (module rel path, function/method name) roots of
    # the dispatch-critical call graph, and names at which traversal
    # stops (sanctioned materialisation points: DecodeHandle.wait is THE
    # place device results come home)
    hot_roots: Tuple[Tuple[str, str], ...] = (
        ("ollama_operator_tpu/runtime/engine.py", "decode_n_launch"),
        ("ollama_operator_tpu/runtime/engine.py", "step"),
        ("ollama_operator_tpu/runtime/scheduler.py", "_fanout"),
    )
    hot_stop_names: Tuple[str, ...] = ("wait", "_watched")
    # modules whose call graphs the hot-path/lock passes resolve into
    graph_scopes: Tuple[str, ...] = ("ollama_operator_tpu/runtime",
                                     "ollama_operator_tpu/server/metrics.py")
    # broadcast-purity: the follower module and its handler entrypoints
    follower_module: str = "ollama_operator_tpu/runtime/follower.py"
    follower_handlers: Tuple[str, ...] = ("run_follower",)
    follower_forbidden: Tuple[str, ...] = (
        "FLIGHT", "TRACER", "Tracer", "get_tracer", "NULL_TRACE",
        "METRICS", "AdmissionQueue", "ADMISSION")
    # determinism: replay-relevant modules (PR 9 bit-identical restart
    # replay depends on these)
    determinism_modules: Tuple[str, ...] = (
        "ollama_operator_tpu/runtime/engine.py",
        "ollama_operator_tpu/runtime/follower.py",
    )
    # exception-hygiene scopes
    exception_scopes: Tuple[str, ...] = (
        "ollama_operator_tpu/runtime",
        "ollama_operator_tpu/server",
        "ollama_operator_tpu/operator",
    )


class Project:
    """Parsed sources + config handed to every pass."""

    def __init__(self, config: LintConfig):
        self.config = config
        self.sources: Dict[str, Source] = {}
        self.parse_errors: List[Finding] = []
        for rel in self._walk():
            path = config.root / rel
            try:
                text = path.read_text(encoding="utf-8")
                self.sources[rel] = Source(path, rel, text)
            except (SyntaxError, UnicodeDecodeError) as e:
                lineno = getattr(e, "lineno", 1) or 1
                self.parse_errors.append(Finding(
                    rel, lineno, "parse", f"cannot parse: {e}"))

    def _walk(self) -> List[str]:
        rels: List[str] = []
        for root in self.config.code_roots:
            p = self.config.root / root
            if p.is_file():
                rels.append(root)
                continue
            for f in sorted(p.rglob("*.py")):
                rel = f.relative_to(self.config.root).as_posix()
                if "__pycache__" in rel:
                    continue
                rels.append(rel)
        # the knob/metric/fault registries may live outside code_roots
        # (fixture trees)
        for extra in (self.config.knobs_module, self.config.metrics_module,
                      self.config.faults_module):
            p = self.config.root / extra
            if p.is_file() and extra not in rels:
                rels.append(extra)
        return rels

    def source(self, rel: str) -> Optional[Source]:
        return self.sources.get(rel)

    def in_scope(self, rel: str, scopes: Iterable[str]) -> bool:
        return any(rel == s or rel.startswith(s.rstrip("/") + "/")
                   for s in scopes)


class Pass:
    """Base class: subclasses set ``id``/``summary`` and implement run()."""

    id: str = ""
    summary: str = ""

    def run(self, project: Project) -> List[Finding]:  # pragma: no cover
        raise NotImplementedError


def _apply_suppressions(project: Project,
                        findings: List[Finding]) -> List[Finding]:
    out: List[Finding] = []
    for f in findings:
        src = project.source(f.path)
        if src is not None:
            hit, reason = src.suppression_for(f.pass_id, f.line)
            if hit:
                f.suppressed = True
                f.suppress_reason = reason
                if reason is None:
                    # a suppression with no justification is a finding of
                    # its own — the reason string IS the policy
                    out.append(Finding(
                        f.path, f.line, "suppression",
                        f"allow({f.pass_id}) has no reason string; write "
                        f"'# lint: allow({f.pass_id}): <why>'"))
        out.append(f)
    return out


def run_passes(config: LintConfig, passes: Iterable[Pass],
               only: Optional[Iterable[str]] = None) -> List[Finding]:
    """Parse the tree once, run every pass, apply suppressions.

    Findings come back sorted by (path, line); ``parse`` errors (files the
    walker could not parse) are always included.
    """
    project = Project(config)
    selected = list(passes)
    if only is not None:
        wanted = set(only)
        selected = [p for p in selected if p.id in wanted]
    findings: List[Finding] = list(project.parse_errors)
    for p in selected:
        findings.extend(p.run(project))
    findings = _apply_suppressions(project, findings)
    findings.sort(key=lambda f: (f.path, f.line, f.pass_id))
    return findings


def summarize(passes: Iterable[Pass],
              findings: List[Finding]) -> List[dict]:
    rows = []
    ids = [p.id for p in passes] + ["suppression", "parse"]
    for pid in ids:
        mine = [f for f in findings if f.pass_id == pid]
        rows.append({
            "id": pid,
            "findings": sum(1 for f in mine if not f.suppressed),
            "suppressed": sum(1 for f in mine if f.suppressed),
        })
    return rows


def render_text(findings: List[Finding], verbose: bool = False) -> str:
    shown = [f for f in findings if verbose or not f.suppressed]
    return "\n".join(f.render() for f in shown)


def render_json(passes: Iterable[Pass], findings: List[Finding]) -> str:
    return json.dumps({
        "version": 1,
        "passes": summarize(passes, findings),
        "findings": [f.to_dict() for f in findings],
    }, indent=2)


def render_github(findings: List[Finding]) -> str:
    out = []
    for f in findings:
        if f.suppressed:
            continue
        kind = "error" if f.severity == "error" else "warning"
        msg = f.message.replace("%", "%25").replace("\n", "%0A")
        out.append(f"::{kind} file={f.path},line={f.line},"
                   f"title=invariant-lint [{f.pass_id}]::{msg}")
    return "\n".join(out)


def render_summary_markdown(passes: Iterable[Pass],
                            findings: List[Finding]) -> str:
    rows = summarize(passes, findings)
    lines = ["### Invariant linter", "",
             "| pass | findings | suppressed |",
             "| --- | ---: | ---: |"]
    for r in rows:
        lines.append(f"| `{r['id']}` | {r['findings']} | "
                     f"{r['suppressed']} |")
    total = sum(r["findings"] for r in rows)
    lines.append("")
    lines.append(f"**{total} unsuppressed finding(s)** "
                 f"({'gate fails' if total else 'gate passes'})")
    return "\n".join(lines)
