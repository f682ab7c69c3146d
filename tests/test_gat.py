"""GAT in ``repro.models.gnn``: the forward pass against a row-by-row NumPy
computation in float64, the parameter tree against the benchmark's plain
reference module, and a 3-hop training run through the device builder."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cliques import topology_matrix
from repro.core.planner import build_plan
from repro.graph.csr import powerlaw_graph
from repro.models import gnn
from repro.models.params import init_from_defs
from repro.train.loop import train_gnn

REF_GAT = os.path.join(os.path.dirname(__file__), "..", "bench", "reference",
                       "gat.py")
B, FAN, D, HID, NC, HEADS = 3, (3, 2), 5, 4, 6, 2


def _cfg(**kw):
    base = dict(model="gat", feat_dim=D, hidden=HID, n_classes=NC,
                fanouts=FAN, batch_size=B, n_heads=HEADS)
    return gnn.GNNConfig(**{**base, **kw})


def _params(cfg, seed=0):
    """Random parameters, biases included (they init to zero)."""
    p = init_from_defs(gnn.defs(cfg), jax.random.PRNGKey(seed))
    leaves, tree = jax.tree.flatten(p)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        x + 0.3 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


def _batch(seed=0):
    """Feature levels (B, f1.., D) and masks; row 0 has no valid hop-1 slot."""
    rng = np.random.default_rng(seed)
    out, shape = {}, (B,)
    for li in range(len(FAN) + 1):
        out[f"feats_{li}"] = rng.normal(size=shape + (D,)).astype(np.float32)
        if li:
            out[f"mask_{li}"] = rng.random(shape) < 0.6
        if li < len(FAN):
            shape = shape + (FAN[li],)
    out["mask_1"][0] = False
    out["mask_1"][1] = True
    return out


def _lrelu(x, slope):
    return x if x > 0 else slope * x


def hand_level(p, last, heads, slope, h_self, h_nb, mask, uniform=False):
    """One level of a GAT layer, row by row and head by head in float64;
    ``uniform=True`` weighs the valid slots and the self loop equally."""
    w, ws = np.float64(p["w"]), np.float64(p["w_skip"])
    a_s, a_d = np.float64(p["att_src"]), np.float64(p["att_dst"])
    b, bs = np.float64(p["b"]), np.float64(p["b_skip"])
    d = w.shape[0]
    hs = np.float64(h_self).reshape(-1, d)
    hn = np.float64(h_nb).reshape(len(hs), -1, d)
    mk = np.asarray(mask).reshape(len(hs), -1)
    rows = []
    for r in range(len(hs)):
        zs = (hs[r] @ w).reshape(heads, -1)
        zj = (hn[r] @ w).reshape(len(mk[r]), heads, -1)
        valid = [j for j in range(len(mk[r])) if mk[r][j]]
        aggs = []
        for h in range(heads):
            cand = [zs[h]] + [zj[j, h] for j in valid]
            e = np.array([_lrelu(a_s[:, h] @ z + a_d[:, h] @ zs[h], slope)
                          for z in cand])
            wt = np.ones(len(cand)) if uniform else np.exp(e - e.max())
            aggs.append(sum(x * z for x, z in zip(wt / wt.sum(), cand)))
        skip = hs[r] @ ws + bs
        if last:
            rows.append(np.mean(aggs, axis=0) + b + skip)
        else:
            x = np.concatenate(aggs) + b + skip
            rows.append(np.where(x > 0, x, np.expm1(np.minimum(x, 0))))
    return np.array(rows).reshape(np.shape(h_self)[:-1] + (-1,))


def hand_forward(cfg, params, batch, uniform=False, slope=0.2):
    L = len(cfg.fanouts)
    h = [batch[f"feats_{li}"] for li in range(L + 1)]
    for li in range(L):
        p = {k: np.asarray(v) for k, v in params[f"layer{li}"].items()}
        h = [hand_level(p, li == L - 1, cfg.n_heads, slope, h[lev],
                        h[lev + 1], batch[f"mask_{lev + 1}"], uniform)
             for lev in range(L - li)]
    return h[0]


def _forward(cfg, params, batch):
    with jax.default_matmul_precision("highest"):
        return np.asarray(gnn.forward(
            cfg, params, {k: jnp.asarray(v) for k, v in batch.items()}))


def test_forward_matches_hand():
    cfg = _cfg()
    params, batch = _params(cfg), _batch()
    got = _forward(cfg, params, batch)
    assert got.shape == (B, NC)  # the last layer averages heads of NC
    np.testing.assert_allclose(got, hand_forward(cfg, params, batch),
                               rtol=1e-5, atol=1e-5)


def test_scores_take_leaky_relu_at_slope_0_2():
    """Negative scores are scaled by 0.2: neither cut to 0 (ReLU) nor
    passed through, and this batch has enough of them to tell."""
    cfg = _cfg()
    params, batch = _params(cfg), _batch()
    got = _forward(cfg, params, batch)
    for slope in (0.0, 1.0):
        assert np.abs(got - hand_forward(cfg, params, batch,
                                         slope=slope)).max() > 1e-3


def _one_level(cfg, params, batch, li):
    """Layer ``li`` on level 0 (the seeds over the hop-1 slots)."""
    p = params[f"layer{li}"]
    with jax.default_matmul_precision("highest"):
        return np.asarray(gnn._gat_layer(
            cfg, p, li == len(cfg.fanouts) - 1,
            jnp.asarray(batch["feats_0"]), jnp.asarray(batch["feats_1"]),
            jnp.asarray(batch["mask_1"])))


def test_row_without_valid_slot_attends_to_itself():
    cfg = _cfg(fanouts=(3,))
    params, batch = _params(cfg), _batch()
    got = _one_level(cfg, params, batch, 0)  # the last (only) layer
    p = {k: np.float64(v) for k, v in params["layer0"].items()}
    x = np.float64(batch["feats_0"][0])
    z = (x @ p["w"]).reshape(HEADS, NC)
    want = z.mean(0) + p["b"] + x @ p["w_skip"] + p["b_skip"]
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-5)


def test_heads_concatenated_on_hidden_layers_averaged_on_last():
    cfg = _cfg()
    params, batch = _params(cfg), _batch()
    hid = _one_level(cfg, params, batch, 0)
    assert hid.shape == (B, HEADS * HID)
    p = {k: np.asarray(v) for k, v in params["layer0"].items()}
    np.testing.assert_allclose(
        hid, hand_level(p, False, HEADS, 0.2, batch["feats_0"],
                        batch["feats_1"], batch["mask_1"]),
        rtol=1e-5, atol=1e-5)
    # the last layer on level 0 takes hidden rows; feed it a level of them
    last_cfg = _cfg(fanouts=(3,), feat_dim=HEADS * HID)
    lp = _params(last_cfg, 3)
    rng = np.random.default_rng(1)
    hb = {"feats_0": rng.normal(size=(B, HEADS * HID)).astype(np.float32),
          "feats_1": rng.normal(size=(B, 3, HEADS * HID)).astype(np.float32),
          "mask_1": batch["mask_1"]}
    out = _one_level(last_cfg, lp, hb, 0)
    assert out.shape == (B, NC)
    q = {k: np.asarray(v) for k, v in lp["layer0"].items()}
    np.testing.assert_allclose(
        out, hand_level(q, True, HEADS, 0.2, hb["feats_0"], hb["feats_1"],
                        hb["mask_1"]), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("leaf", ["b", "b_skip", "w_skip"])
def test_skip_and_both_biases_count(leaf):
    cfg = _cfg()
    params, batch = _params(cfg), _batch()
    last = f"layer{len(FAN) - 1}"
    zeroed = {**params, last: {**params[last],
                               leaf: jnp.zeros_like(params[last][leaf])}}
    a, b = _forward(cfg, params, batch), _forward(cfg, zeroed, batch)
    assert np.abs(a - b).max() > 1e-3
    np.testing.assert_allclose(b, hand_forward(cfg, zeroed, batch),
                               rtol=1e-5, atol=1e-5)


def test_attention_weights_matter():
    cfg = _cfg()
    params, batch = _params(cfg), _batch()
    want = hand_forward(cfg, params, batch)
    assert np.abs(hand_forward(cfg, params, batch, uniform=True)
                  - want).max() > 1e-2


def _reference_module():
    spec = importlib.util.spec_from_file_location("bench_ref_gat", REF_GAT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_defs_are_the_reference_leaves():
    ref = _reference_module()
    cfg = gnn.GNNConfig(model="gat", feat_dim=100, hidden=128, n_classes=47,
                        fanouts=(10, 10, 10), n_heads=4)
    got = jax.tree_util.tree_flatten_with_path(
        gnn.defs(cfg), is_leaf=lambda x: isinstance(x, gnn.Def))[0]
    want, d_in = [], 100
    for li in range(3):
        leaves, d_in = ref.layer_params(li, 3, d_in, 128, 47, n_heads=4)
        want += [((f"layer{li}", k), shape, init)
                 for k, (shape, init) in sorted(leaves.items())]
    assert [(tuple(k.key for k in path), d.shape, d.init)
            for path, d in got] == want
    assert not ref.has_head(n_heads=4)


@pytest.mark.parametrize("model,leaves", [
    ("sage", {"w_self": (8, 16), "w_neigh": (8, 16), "b": (16,)}),
    ("gcn", {"w": (8, 16), "b": (16,)})])
def test_sage_and_gcn_defs_unchanged(model, leaves):
    cfg = gnn.GNNConfig(model=model, feat_dim=8, hidden=16, n_classes=5,
                        fanouts=(3, 2))
    d = gnn.defs(cfg)
    assert set(d) == {"layer0", "layer1", "head"}
    assert {k: v.shape for k, v in d["layer0"].items()} == leaves
    assert d["layer1"]["b"].shape == (16,)
    assert d["head"].shape == (16, 5)


def test_three_hop_training_on_the_device_builder():
    g = powerlaw_graph(3000, 8, seed=5, feat_dim=16, n_classes=8)
    plan = build_plan(g, topology_matrix("tpu-pod", 1), mem_per_device=400_000,
                      batch_size=32, fanouts=(4, 3, 2), seed=0)
    cfg = gnn.GNNConfig(model="gat", feat_dim=16, hidden=8, n_classes=8,
                        fanouts=(4, 3, 2), batch_size=32, lr=3e-3, n_heads=2)
    res = train_gnn(g, plan, cfg, steps=30, seed=1, backend="device",
                    gather="xla")
    assert res.backend == "device"
    assert np.isfinite(res.losses).all()
    assert np.mean(res.losses[-5:]) < np.mean(res.losses[:5]) - 0.05
