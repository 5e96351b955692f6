"""Mesh construction for the production topology.

Two views of the same chips:

- :func:`make_production_mesh` — the assignment-fixed axes
  ``("data", "model")`` = 16x16 single pod, or ``("pod", "data", "model")`` =
  2x16x16 multi-pod.
- :func:`make_worker_mesh` — the framework's refactoring
  ``data = worker x fsdp`` (DESIGN.md §4): gossip replicas along ``worker``
  (outermost/slowest, across pods in the multi-pod case), FSDP within a
  replica group along ``fsdp``, tensor-parallel along ``model``.

Both are FUNCTIONS so importing this module never touches jax device state.
"""
from __future__ import annotations

import jax

from jax.sharding import AbstractMesh, AxisType

from repro.common.config import MeshConfig

# gossip/"worker" axes of the worker mesh, outermost first
WORKER_AXES = ("pod", "worker")
REPLICA_AXES = ("fsdp", "model")   # axes *within* one gossip replica group


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_worker_mesh(cfg: MeshConfig):
    """Production mesh with the data axis factored into (worker, fsdp)."""
    shape = (cfg.pods, cfg.workers_per_pod, cfg.fsdp, cfg.model)
    axes = ("pod", "worker", "fsdp", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_abstract_worker_mesh(cfg: MeshConfig):
    """Device-free stand-in with the worker-mesh axes: shape math (specs,
    input_specs) without owning 256 placeholder devices."""
    return AbstractMesh((cfg.pods, cfg.workers_per_pod, cfg.fsdp, cfg.model),
                        ("pod", "worker", "fsdp", "model"))


def make_host_mesh(num_workers: int = 1):
    """Single-host CPU mesh used by tests/examples: all real devices on one
    worker axis (typically just 1 device)."""
    n = len(jax.devices())
    assert n % num_workers == 0, (n, num_workers)
    shape = (1, num_workers, n // num_workers, 1)
    return jax.make_mesh(shape, ("pod", "worker", "fsdp", "model"),
                         axis_types=(AxisType.Auto,) * 4)
