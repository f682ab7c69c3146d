"""GNN train loop: learning, checkpoint/restart, straggler monitor."""
import tempfile

import jax
import numpy as np
import pytest

from repro.core.cliques import topology_matrix
from repro.core.planner import build_plan
from repro.graph.csr import powerlaw_graph
from repro.models.gnn import GNNConfig
from repro.train.loop import train_gnn
from repro.train.pipeline import StragglerMonitor


@pytest.fixture(scope="module")
def setup():
    g = powerlaw_graph(6000, 10, seed=4, feat_dim=32, label_signal=2.0) \
        if False else powerlaw_graph(6000, 10, seed=4, feat_dim=32)
    plan = build_plan(g, topology_matrix("nv2"), mem_per_device=1_000_000,
                      batch_size=256, seed=0)
    return g, plan


def test_training_learns(setup):
    g, plan = setup
    cfg = GNNConfig(feat_dim=32, hidden=64, batch_size=128, fanouts=(5, 3),
                    lr=3e-3)
    res = train_gnn(g, plan, cfg, steps=60, seed=0)
    assert res.losses[-1] < res.losses[0] - 0.1
    assert res.accs[-1] > 0.2  # 32 classes, random = 0.031


def test_checkpoint_restart(setup):
    g, plan = setup
    cfg = GNNConfig(feat_dim=32, hidden=32, batch_size=64, fanouts=(4, 2))
    with tempfile.TemporaryDirectory() as d:
        train_gnn(g, plan, cfg, steps=20, checkpoint_dir=d,
                  checkpoint_every=10)
        r2 = train_gnn(g, plan, cfg, steps=30, checkpoint_dir=d, resume=True)
        assert r2.steps == 10  # resumed from step 20


def test_gcn_variant(setup):
    g, plan = setup
    cfg = GNNConfig(model="gcn", feat_dim=32, hidden=32, batch_size=64,
                    fanouts=(4, 2))
    res = train_gnn(g, plan, cfg, steps=10)
    assert np.isfinite(res.losses).all()


def test_straggler_monitor():
    m = StragglerMonitor(threshold=2.0)
    for _ in range(10):
        m.record(0.1)
    assert m.record(0.5) is True
    assert m.summary()["stragglers"] == 1


LOOKAHEAD_STEPS = 5
LOOKAHEAD_CASES = ("plain", "resume", "worker_fault", "refresh")


def _lookahead_run(g, plan, cfg, backend, case, ckpt_dir):
    """``train_gnn`` for ``LOOKAHEAD_STEPS`` steps as ``case`` says: one
    call; a step-1 call and then a resumed one; one whose prefetch worker
    dies before step 2 and is respawned; or one with the online cache
    manager on.  A checkpoint is taken at every step boundary.  Returns
    (losses, final parameters and optimizer state, counter, each call's
    ``prefetch.sample_ahead``)."""
    from repro.core.unified_cache import TrafficCounter
    from repro.models.gnn import defs as gnn_defs
    from repro.models.params import init_from_defs
    from repro.obs import Telemetry, TelemetryConfig
    from repro.train.checkpoint import (latest_resumable_checkpoint,
                                        restore_checkpoint)
    from repro.train.optimizer import adamw
    from repro.train.resilience import (FaultPlan, FaultSpec,
                                        ResilienceConfig)

    counter = TrafficCounter.for_plan(plan)
    kw = dict(seed=5, backend=backend, gather="xla", counter=counter,
              checkpoint_dir=ckpt_dir, checkpoint_every=1)
    if case == "worker_fault":
        kw["resilience"] = ResilienceConfig(
            fault_plan=FaultPlan([FaultSpec("prefetch_build", step=2)]),
            worker_restarts=1)
    if case == "refresh":
        kw["refresh_interval"] = 3
    calls = ([dict(steps=1), dict(steps=LOOKAHEAD_STEPS, resume=True)]
             if case == "resume" else [dict(steps=LOOKAHEAD_STEPS)])
    losses, ahead = [], []
    for call in calls:
        tele = Telemetry(TelemetryConfig(jax_annotations=False))
        res = train_gnn(g, plan, cfg, telemetry=tele, **kw, **call)
        losses += list(res.losses)
        n = tele.registry.counter("prefetch.sample_ahead").total
        assert n == res.pipeline["sample_ahead"]
        ahead.append(n)
        if case == "worker_fault":
            assert res.pipeline["worker_restarts"] == 1
    params = init_from_defs(gnn_defs(cfg), jax.random.PRNGKey(0))
    like = (params, adamw(cfg.lr).init(params))
    path = latest_resumable_checkpoint(ckpt_dir, like=like)
    step, state = restore_checkpoint(path, like)
    assert step == LOOKAHEAD_STEPS
    return losses, state, counter, ahead


@pytest.mark.parametrize("case", LOOKAHEAD_CASES)
def test_sampling_lookahead_is_bitwise_the_host_pipeline(setup, case,
                                                        tmp_path,
                                                        monkeypatch):
    """The device backend dispatches batch k+1's sampling chain inside
    batch k's build.  Its losses, final parameters, traffic counts and
    the sampler RNG states its checkpoints carry equal the host backend's
    bit for bit; ``prefetch.sample_ahead`` counts the builds that found
    their chain dispatched: all but each device's first in a call, none
    under the online cache manager's refresh hook and none on the host
    backend."""
    from repro.train.checkpoint import AsyncCheckpointer

    saved = []
    real_save = AsyncCheckpointer.save

    def save(self, step, tree, runtime=None):
        saved.append((step, runtime["rng"]))
        return real_save(self, step, tree, runtime=runtime)

    monkeypatch.setattr(AsyncCheckpointer, "save", save)
    g, plan = setup
    cfg = GNNConfig(feat_dim=32, hidden=32, batch_size=64, fanouts=(4, 2))
    l_h, s_h, c_h, a_h = _lookahead_run(g, plan, cfg, "host", case,
                                        str(tmp_path / "host"))
    rng_h, saved[:] = list(saved), []
    l_d, s_d, c_d, a_d = _lookahead_run(g, plan, cfg, "device", case,
                                        str(tmp_path / "device"))
    assert len(saved) >= LOOKAHEAD_STEPS
    assert saved == rng_h
    np.testing.assert_array_equal(l_h, l_d)
    assert len(l_d) == LOOKAHEAD_STEPS
    for a, b in zip(jax.tree.leaves(s_h), jax.tree.leaves(s_d)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (c_h.feature_requests, c_h.feature_hits, c_h.topo_requests,
            c_h.topo_hits, c_h.pcie_transactions, c_h.host_sampled_edges) \
        == (c_d.feature_requests, c_d.feature_hits, c_d.topo_requests,
            c_d.topo_hits, c_d.pcie_transactions, c_d.host_sampled_edges)
    np.testing.assert_array_equal(c_h.bytes_matrix, c_d.bytes_matrix)
    np.testing.assert_array_equal(c_h.topo_bytes_matrix,
                                  c_d.topo_bytes_matrix)
    n_dev = len(plan.partition.tablets)
    want = {"plain": [LOOKAHEAD_STEPS - 1],
            "resume": [0, LOOKAHEAD_STEPS - 2],
            "worker_fault": [LOOKAHEAD_STEPS - 1],
            "refresh": [0]}[case]
    assert a_d == [n * n_dev for n in want]
    assert a_h == [0] * len(want)


def test_levels_altered_fault_reaches_lookahead_batches(setup, monkeypatch):
    """The benchmark's ``levels_altered`` fault wraps the ``resolve`` that
    ``repro.train.batch.cache_sample_dispatch`` returns.  Batches whose
    chain the lookahead dispatched during the previous build must train
    on its spoiled levels too: every level below the seeds equals the
    clean run's with every 16th id dropped."""
    import os

    from repro.train.batch import DeviceBatchBuilder

    monkeypatch.syspath_prepend(
        os.path.join(os.path.dirname(__file__), "..", "bench"))
    from benchlib import faults

    g, plan = setup
    cfg = GNNConfig(feat_dim=32, hidden=32, batch_size=64, fanouts=(4, 2))
    seen = {}
    real_fill = DeviceBatchBuilder.fill_spec

    def fill_spec(self, spec, step=None):
        seen[(self.dev, step)] = [lvl.copy() for lvl in spec.levels]
        return real_fill(self, spec, step=step)

    monkeypatch.setattr(DeviceBatchBuilder, "fill_spec", fill_spec)
    steps = 4
    train_gnn(g, plan, cfg, steps=steps, seed=2, backend="device",
              gather="xla")
    clean, seen = seen, {}
    with faults.levels_altered():
        res = train_gnn(g, plan, cfg, steps=steps, seed=2,
                        backend="device", gather="xla")
    n_dev = len(plan.partition.tablets)
    assert res.pipeline["sample_ahead"] == n_dev * (steps - 1)
    assert sorted(seen) == sorted(clean) and len(seen) == n_dev * steps
    for key, levels in seen.items():
        want = clean[key]
        np.testing.assert_array_equal(levels[0], want[0])
        for got, lv in zip(levels[1:], want[1:]):
            drop = (np.arange(lv.size) % 16 == 0).reshape(lv.shape)
            np.testing.assert_array_equal(got, np.where(drop, -1, lv))
            assert (got != lv).any(), key
