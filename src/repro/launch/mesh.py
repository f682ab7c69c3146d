"""Production mesh construction + the clique execution mesh.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The single-pod mesh is 16x16 = 256 chips
("data", "model"); the multi-pod mesh adds a leading "pod" axis: 2 pods =
512 chips, pure data parallelism across the DCN-connected pods.

``make_clique_mesh`` builds the 1-D mesh the clique-parallel GNN executor
runs on: one mesh position per device of one NVLink/ICI clique, axis name
``"clique"``.  Cache shard views are laid out along this axis and the
routed gather / gradient psum reduce over it.  On CPU the clique is
simulated by launching with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax import.

``make_hierarchical_mesh`` is its 2-D generalization — the execution mesh
of Legion's full hierarchical partitioning (paper §4.1): axes
``("pod", "clique")``, one row per NVLink/ICI clique of the
``PartitionPlan`` and one column per device within its clique.  All
cache/batch traffic stays within a row (``psum`` over ``"clique"`` — the
routed gather's peer exchange never crosses cliques), while gradient
synchronization additionally reduces over ``"pod"`` (the data-parallel
inter-clique axis, PCIe/DCN in hardware).  A single-clique plan is the
degenerate ``K_c=1`` case of the same mesh — there is no separate 1-D
execution path in the trainer.  Every mesh uses ``AxisType.Auto`` axes.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh

CLIQUE_AXIS = "clique"
POD_AXIS = "pod"


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devices)}. "
            "The dry-run entrypoint must set XLA_FLAGS="
            "--xla_force_host_platform_device_count=512 before importing jax."
        )
    import numpy as np

    dev_array = np.asarray(devices[:n]).reshape(shape)
    return Mesh(dev_array, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    """Small mesh for tests (spawn with a fake device-count XLA flag)."""
    import numpy as np

    n = math.prod(shape)
    dev_array = np.asarray(jax.devices()[:n]).reshape(shape)
    return Mesh(dev_array, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_clique_mesh(n_devices: Optional[int] = None,
                     axis_name: str = CLIQUE_AXIS,
                     devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over the devices of one interconnect clique.

    ``devices`` pins specific jax devices (in clique-local order);
    otherwise the first ``n_devices`` of ``jax.devices()`` are used.  The
    sharded trainer lays the stacked cache shards, batch parts, and routed
    gather outputs along this single axis, so position ``g`` of every
    sharded array lives on the clique-local device ``g`` that owns cache
    partition ``g``.
    """
    import numpy as np

    if devices is None:
        avail = jax.devices()
        n = len(avail) if n_devices is None else n_devices
        if len(avail) < n:
            raise RuntimeError(
                f"make_clique_mesh: need {n} devices, have {len(avail)}. "
                "Simulate a clique on CPU with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={n} (set before "
                "importing jax).")
        devices = avail[:n]
    dev_array = np.asarray(list(devices))
    return Mesh(dev_array, (axis_name,), axis_types=(AxisType.Auto,))


def make_hierarchical_mesh(cliques: Sequence[Sequence[int]],
                           axis_names: Sequence[str] = (POD_AXIS, CLIQUE_AXIS),
                           devices: Optional[Sequence] = None) -> Mesh:
    """2-D ``(pod, clique)`` execution mesh built from a partition plan's
    clique list (``PartitionPlan.cliques``).

    Row ``ci`` of the mesh is clique ``ci``; within a row, column ``gi``
    is the clique-local device that owns cache partition ``gi`` of that
    clique's unified cache.  ``devices`` pins specific jax devices in
    (clique-major) row order; otherwise the first ``K_c * K_g`` of
    ``jax.devices()`` are used.  The clique list must be uniform — a 2-D
    mesh cannot express ragged cliques (run a degraded/mixed reservation
    as separate jobs, or replan it with ``replan_on_topology_change``).
    """
    import numpy as np

    sizes = sorted({len(c) for c in cliques})
    if not cliques or sizes[0] == 0:
        raise ValueError("make_hierarchical_mesh: need at least one "
                         "non-empty clique")
    if len(sizes) != 1:
        raise ValueError(
            f"make_hierarchical_mesh: clique sizes {[len(c) for c in cliques]}"
            " are ragged; the (pod, clique) mesh needs one uniform K_g")
    k_c, k_g = len(cliques), sizes[0]
    n = k_c * k_g
    if devices is None:
        avail = jax.devices()
        if len(avail) < n:
            raise RuntimeError(
                f"make_hierarchical_mesh: need {n} devices for a "
                f"{k_c}x{k_g} (pod, clique) mesh, have {len(avail)}. "
                "Simulate on CPU with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={n} (set before "
                "importing jax).")
        devices = avail[:n]
    if len(devices) != n:
        raise ValueError(
            f"make_hierarchical_mesh: {len(devices)} devices pinned for a "
            f"{k_c}x{k_g} mesh (need exactly {n})")
    dev_array = np.asarray(list(devices)).reshape(k_c, k_g)
    return Mesh(dev_array, tuple(axis_names), axis_types=(AxisType.Auto,) * 2)

