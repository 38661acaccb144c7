"""Pass catalog.  Adding a pass: subclass core.Pass, give it a unique
kebab-case ``id`` and a one-line ``summary``, implement ``run(project)``
returning Findings, and append an instance to ALL_PASSES.  Fixture
coverage in tests/fixtures/lint/ + tests/test_invariant_lint.py is part
of the definition of done (see CONTRIBUTING.md)."""

from .determinism import DeterminismPass
from .exception_hygiene import ExceptionHygienePass
from .fault_catalog import FaultCatalogPass
from .follower_purity import FollowerPurityPass
from .host_sync import HostSyncPass
from .knob_registry import KnobRegistryPass
from .lock_order import LockOrderPass
from .metrics_discipline import MetricsDisciplinePass
from .trace_env import TraceEnvPass

ALL_PASSES = [
    KnobRegistryPass(),
    TraceEnvPass(),
    MetricsDisciplinePass(),
    FaultCatalogPass(),
    HostSyncPass(),
    LockOrderPass(),
    FollowerPurityPass(),
    DeterminismPass(),
    ExceptionHygienePass(),
]

__all__ = ["ALL_PASSES", "KnobRegistryPass", "MetricsDisciplinePass",
           "FaultCatalogPass", "HostSyncPass", "LockOrderPass",
           "FollowerPurityPass", "DeterminismPass",
           "ExceptionHygienePass", "TraceEnvPass"]
