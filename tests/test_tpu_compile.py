"""Compile the feature path's Pallas kernels for a TPU v5e without a chip.

Every compile targets a *described* ``v5e:2x2`` topology (no device is
attached), at the sizes the paper's setting produces: one GraphSAGE batch
(fan-outs 25x10, batch 8000) on the 200k-vertex ``PR`` instance has about
190k unique vertices at D=100, lane-padded to 128, and a refresh scatters
into a cache of 1M rows.  Mosaic's tiling and memory limits show up here
and nowhere in the interpret-mode tests.  Each compiled program must hold
the kernel (``tpu_custom_call``).

The topology is described inside a fixture — never at import time — so
that under pytest-xdist only the worker that runs this file loads the TPU
compiler, and every worker collects the same tests.
"""
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_batch, gather, scatter

D, DP = 100, 128         # PR feature dim, and its lane-padded width
UNIQUE = 190_000         # unique rows of one paper-setting batch
CACHE_ROWS = 1 << 20     # a 1M-row feature cache
# chip_smoke.py's first batch on one chip (hidden 256, fan-outs 25x10,
# batch 8000, seed 0): bucket-rounded id and miss counts, cached rows
SMOKE_IDS, SMOKE_MISS, SMOKE_CACHE = 188_160, 88_320, 101_250
BATCH, FANOUTS = 8000, (25, 10)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device lands in the persistent cache but
    # can never be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled(monkeypatch):
    """Compile ``fn`` for the described chip.  The kernels pick interpret
    mode from the process's own (CPU) backend, so the test steers them to
    Mosaic here."""
    for mod in (gather, fused_batch, scatter):
        monkeypatch.setattr(mod, "interpret_default", lambda: False)

    def compile_text(fn, *args, **static):
        return jax.jit(fn, static_argnames=tuple(static)).lower(
            *args, **static).compile().as_text()

    return compile_text


def _s(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_gather_rows_compiles(one_chip, compiled):
    text = compiled(gather.gather_rows_pallas,
                    _s(one_chip, (CACHE_ROWS, DP)),
                    _s(one_chip, (UNIQUE,), jnp.int32))
    assert "tpu_custom_call" in text


def test_fused_gather_overlay_compiles(one_chip, compiled):
    text = compiled(fused_batch.fused_gather_overlay_pallas,
                    _s(one_chip, (CACHE_ROWS, DP)),
                    _s(one_chip, (UNIQUE,), jnp.int32),
                    _s(one_chip, (UNIQUE // 2, DP)),
                    _s(one_chip, (UNIQUE,), jnp.int32))
    assert "tpu_custom_call" in text


def test_scatter_rows_compiles(one_chip, compiled):
    text = compiled(scatter.scatter_rows_pallas,
                    _s(one_chip, (CACHE_ROWS, DP)),
                    _s(one_chip, (UNIQUE,), jnp.int32),
                    _s(one_chip, (UNIQUE, DP)))
    assert "tpu_custom_call" in text


def test_fused_finalize_compiles(one_chip, compiled):
    """The whole one-dispatch device phase of the smoke's first batch."""
    from repro.train.batch import _get_fused_finalize

    f1, f2 = FANOUTS
    levels = [(BATCH,), (BATCH, f1), (BATCH, f1, f2)]
    pos = tuple(_s(one_chip, (math.prod(shp),), jnp.int32) for shp in levels)
    valid = tuple(_s(one_chip, shp, jnp.bool_) for shp in levels)
    text = compiled(_get_fused_finalize(),
                    _s(one_chip, (SMOKE_CACHE, DP)),
                    _s(one_chip, (SMOKE_IDS,), jnp.int32),
                    _s(one_chip, (SMOKE_MISS, DP)),
                    _s(one_chip, (SMOKE_IDS,), jnp.int32),
                    _s(one_chip, (BATCH,), jnp.int32), pos, valid,
                    impl="pallas", D=D)
    assert "tpu_custom_call" in text


def test_sharded_step_compiles_for_four_chips(topo, compiled):
    """The whole ``backend="sharded"`` train step on the 1x4 (pod, clique)
    mesh at chip_smoke.py --chips 4's shapes: routed gather kernel,
    all-gather/psum exchange, forward, backward and optimizer."""
    import dataclasses

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs.legion_gnn import GRAPHSAGE
    from repro.launch.mesh import CLIQUE_AXIS, POD_AXIS, make_hierarchical_mesh
    from repro.models.gnn import defs
    from repro.models.params import init_from_defs
    from repro.train.loop import _make_sharded_step
    from repro.train.optimizer import adamw

    mesh = make_hierarchical_mesh([[0, 1, 2, 3]], devices=topo.devices)
    cfg = dataclasses.replace(GRAPHSAGE, feat_dim=D)
    opt = adamw(cfg.lr)
    params = jax.eval_shape(
        lambda: init_from_defs(defs(cfg), jax.random.PRNGKey(0)))
    rep = NamedSharding(mesh, P())
    repl = jax.tree.map(lambda a: _s(rep, a.shape, a.dtype),
                        (params, jax.eval_shape(opt.init, params)))
    grid = NamedSharding(mesh, P(POD_AXIS, CLIQUE_AXIS))
    per_dev, f1, f2 = BATCH // 4, *FANOUTS
    ids = 123_648  # bucket-rounded unique ids of the largest clique device
    packed = {"owner": (ids,), "local": (ids,), "labels": (per_dev,),
              "pos_0": (per_dev,), "pos_1": (per_dev * f1,),
              "pos_2": (per_dev * f1 * f2,)}
    packed = {k: _s(grid, (1, 4) + v, jnp.int32) for k, v in packed.items()}
    packed["miss_rows"] = _s(grid, (1, 4, ids, D))
    for li, shp in enumerate([(per_dev,), (per_dev, f1), (per_dev, f1, f2)]):
        packed[f"valid_{li}"] = _s(grid, (1, 4) + shp, jnp.bool_)
    step = _make_sharded_step(cfg, opt, mesh, (POD_AXIS, CLIQUE_AXIS),
                              n_total=BATCH, feat_dim=D, impl="pallas")
    text = step.lower(*repl, _s(grid, (1, 4, 25_312, DP)),
                      packed).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
