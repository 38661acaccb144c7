"""trace-env: traced code reads the environment in one place only.

``ops/`` and ``models/`` are what ``jax.jit`` traces. A read of
``os.environ`` there runs at TRACE time: its value is no part of a jit
cache key, of a persistent compile-cache key or of the ``warm_plan``
event, so two programs traced under different values are told apart by
nothing, and a program traced before the variable changed keeps serving.
Until PR 31 six ``TPU_*`` variables chose between attention kernels that
way. What such code needs from outside reaches it through ``ModelConfig``
(a static argument of every program) or the engine's constructor.

The one sanctioned read is the resolver of ``OLLAMA_TPU_KERNELS``
(``LintConfig.trace_env_resolvers``), which fills the ``auto`` of
``ModelConfig.kernels``. Flagged: any mention of ``os.environ`` /
``os.getenv`` (or those names imported bare) in the scoped packages
outside a resolver's body.
"""

from __future__ import annotations

import ast
from typing import List, Set

from ..astutil import FUNC_NODES
from ..core import Finding, Pass, Project

ENV_NAMES = {"environ", "getenv"}


class TraceEnvPass(Pass):
    id = "trace-env"
    summary = ("no environment read under ops/ or models/ outside the "
               "one kernel-mode resolver")

    def run(self, project: Project) -> List[Finding]:
        cfg = project.config
        findings: List[Finding] = []
        for rel, src in sorted(project.sources.items()):
            if not project.in_scope(rel, cfg.trace_env_scopes):
                continue
            allowed = {name for mod, name in cfg.trace_env_resolvers
                       if mod == rel}
            exempt = self._resolver_nodes(src.tree, allowed)
            for node in ast.walk(src.tree):
                if id(node) in exempt or not self._is_env(node):
                    continue
                findings.append(Finding(
                    rel, node.lineno, self.id,
                    "environment read in traced code: a trace-time read "
                    "is not a jit cache key — take the value from "
                    "ModelConfig or the engine, or resolve it in "
                    + " / ".join(f"{m}:{n}"
                                 for m, n in cfg.trace_env_resolvers)))
        return findings

    @staticmethod
    def _is_env(node: ast.AST) -> bool:
        if isinstance(node, ast.Attribute):
            return node.attr in ENV_NAMES
        if isinstance(node, ast.Name):
            return (node.id in ENV_NAMES
                    and isinstance(node.ctx, ast.Load))
        return False

    @staticmethod
    def _resolver_nodes(tree: ast.AST, allowed: Set[str]) -> Set[int]:
        """ids of every node inside a sanctioned resolver's body."""
        out: Set[int] = set()
        for node in ast.walk(tree):
            if isinstance(node, FUNC_NODES) and node.name in allowed:
                out.update(id(n) for n in ast.walk(node))
        return out
