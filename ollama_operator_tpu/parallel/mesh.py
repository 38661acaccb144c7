"""Device mesh construction.

The reference's only parallelism is K8s replica fan-out
(/root/reference/pkg/model/model.go:72 — spec.replicas → Deployment
replicas); every other axis here is new TPU-native capability (SURVEY.md
§2.3). Axis conventions used across the framework:

  dp — data parallel (batch). Maps across slices / DCN, or within a slice.
  pp — pipeline parallel (layer stages; p2p ppermute, tolerates DCN).
  sp — sequence parallel (ring attention for long context).
  ep — expert parallel (MoE experts resident per device group).
  tp — tensor parallel (heads / ffn / vocab). Must ride ICI.

Axis order is outermost→innermost by communication cost tolerance: tp is
innermost (latency-critical all-reduce every layer → physically adjacent
ICI neighbours), ep next (per-layer combine-reduce), sp next (ring
per layer), pp (one p2p per stage boundary), dp outermost (gradient-free
serving: no traffic at all).

Single-chip and CPU-test configs are just degenerate meshes (1×…×1 or
8-device CPU meshes via --xla_force_host_platform_device_count=8).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("dp", "pp", "sp", "ep", "tp")


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """How to lay devices out over the 5 serving axes (any may be 1)."""

    dp: int = 1
    sp: int = 1
    tp: int = 1
    pp: int = 1
    ep: int = 1

    @property
    def n_devices(self) -> int:
        return self.dp * self.pp * self.sp * self.ep * self.tp

    @property
    def dims(self) -> tuple:
        """Sizes in AXES order."""
        return (self.dp, self.pp, self.sp, self.ep, self.tp)

    @staticmethod
    def for_devices(n: int, tp: Optional[int] = None, sp: int = 1,
                    pp: int = 1, ep: int = 1) -> "MeshPlan":
        """Default plan: all tensor-parallel unless told otherwise."""
        if tp is None:
            tp = n // (sp * pp * ep)
        dp = n // (tp * sp * pp * ep)
        plan = MeshPlan(dp=dp, sp=sp, tp=tp, pp=pp, ep=ep)
        assert plan.n_devices == n, f"{plan} does not cover {n} devices"
        return plan


def make_mesh(plan: MeshPlan, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    if devices is None:
        devices = jax.devices()
    if len(devices) < plan.n_devices:
        raise ValueError(f"need {plan.n_devices} devices, have {len(devices)}")
    arr = np.array(devices[: plan.n_devices]).reshape(plan.dims)
    return Mesh(arr, AXES)
