"""The layer module interface of ``benchlib/refgnn.py``: the sage and gcn
modules give what the reference gave when it took the masked mean itself,
and a neighbour-weighted module that lives only in this file (one
attention head over the sampled slots, a last layer as wide as the
classes, no head, a required ``model_args`` key) runs through the
reference's forward pass, parameter shapes, training step and the step's
FLOP count with no benchmark file edited."""
import json
import os

import _benchpath  # noqa: F401
import numpy as np
import pytest
from _benchpath import BENCH

from benchlib import counts, graphgen, harness, refgnn

OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
       "weight_decay": 0.01, "grad_clip": 1.0}


# ---- the reference's layers as they were, with the mean taken inline ----

OLD_LAYER_PARAMS = {
    "sage": {"b": (lambda d_in, d_out: (d_out,), "zeros"),
             "w_neigh": (lambda d_in, d_out: (d_in, d_out), "normal"),
             "w_self": (lambda d_in, d_out: (d_in, d_out), "normal")},
    "gcn": {"b": (lambda d_in, d_out: (d_out,), "zeros"),
            "w": (lambda d_in, d_out: (d_in, d_out), "normal")},
}


def old_param_shapes(name, feat_dim, hidden, n_classes, n_layers):
    out, d_in = {}, feat_dim
    for li in range(n_layers):
        out[f"layer{li}"] = {k: (shape(d_in, hidden), init) for k, (
            shape, init) in OLD_LAYER_PARAMS[name].items()}
        d_in = hidden
    out["head"] = ((d_in, n_classes), "normal")
    return out


def old_layer(name, p, h_self, h_agg, precision):
    import jax
    import jax.numpy as jnp

    def dot(x, w):
        return jnp.matmul(x, w.astype(x.dtype), precision=precision)

    if name == "sage":
        return jax.nn.relu(dot(h_self, p["w_self"])
                           + dot(h_agg, p["w_neigh"])
                           + p["b"].astype(h_self.dtype))
    x = 0.5 * (h_self + h_agg)
    return jax.nn.relu(jnp.matmul(x, p["w"].astype(x.dtype),
                                  precision=precision)
                       + p["b"].astype(x.dtype))


def old_forward(name):
    def forward(model, params, feats, masks, precision, model_args=None):
        import jax.numpy as jnp

        n_layers = len(feats) - 1
        h = list(feats)
        for li in range(n_layers):
            p = params[f"layer{li}"]
            new = []
            for lev in range(n_layers - li):
                m = masks[lev + 1].astype(h[lev + 1].dtype)[..., None]
                agg = ((h[lev + 1] * m).sum(axis=-2)
                       / jnp.maximum(m.sum(axis=-2), 1.0))
                new.append(old_layer(name, p, h[lev], agg, precision))
            h = new
        return jnp.matmul(h[0], params["head"].astype(h[0].dtype),
                          precision=precision)

    return forward


# ---- a neighbour-weighted layer module, defined only here ----------------

class Attention:
    """One attention head: every row of the level and of the level below is
    projected by ``w``; a neighbour's weight is the softmax, over the valid
    slots, of its projection against the vector ``a`` over
    ``temperature``; the layer adds the self row's projection, the weighted
    neighbours and ``b``, then relu, except on the last layer, which is
    ``n_classes`` wide and has no head after it."""

    @staticmethod
    def layer_params(li, n_layers, d_in, hidden, n_classes, *, temperature):
        d_out = n_classes if li == n_layers - 1 else hidden
        return {"a": ((d_out,), "normal"), "b": ((d_out,), "zeros"),
                "w": ((d_in, d_out), "normal")}, d_out

    @staticmethod
    def has_head(*, temperature):
        return False

    @staticmethod
    def layer(li, n_layers, p, h_self, h_neigh, mask, precision, *,
              temperature):
        import jax
        import jax.numpy as jnp

        def dot(x, w):
            return jnp.matmul(x, w.astype(x.dtype), precision=precision)

        z = dot(h_neigh, p["w"])
        s = jnp.where(mask, dot(z, p["a"]) / temperature, -1e9)
        e = jnp.exp(s - s.max(axis=-1, keepdims=True)) * mask
        alpha = e / jnp.maximum(e.sum(axis=-1, keepdims=True), 1e-30)
        out = (dot(h_self, p["w"]) + (alpha[..., None] * z).sum(axis=-2)
               + p["b"].astype(h_self.dtype))
        return out if li == n_layers - 1 else jax.nn.relu(out)

    @staticmethod
    def layer_flops(li, n_layers, rows, rows_below, d_in, d_out, grad_in, *,
                    temperature):
        proj = 2 * (rows + rows_below) * d_in * d_out  # self and neighbours
        att = 2 * rows_below * d_out  # the scores, or the weighted sum
        return proj + 2 * att, proj + 4 * att + (proj if grad_in else 0)


ARGS = {"temperature": 2.0}


def hand_layer(last, p, h_self, h_neigh, mask, temperature, weighted=True):
    """One level of ``Attention.layer`` row by row in float64; with
    ``weighted=False`` the plain masked mean of the projections."""
    w, a, b = (np.asarray(p[k], np.float64) for k in ("w", "a", "b"))
    hs = np.asarray(h_self, np.float64).reshape(-1, w.shape[0])
    hn = np.asarray(h_neigh, np.float64).reshape(len(hs), -1, w.shape[0])
    mk = np.asarray(mask).reshape(len(hs), -1)
    out = np.zeros((len(hs), w.shape[1]))
    for r in range(len(hs)):
        z = hn[r] @ w
        ok = mk[r]
        agg = np.zeros(w.shape[1])
        if ok.any():
            if weighted:
                s = z[ok] @ a / temperature
                wt = np.exp(s - s.max())
                agg = (wt[:, None] * z[ok]).sum(0) / wt.sum()
            else:
                agg = z[ok].mean(0)
        out[r] = hs[r] @ w + agg + b
    out = out if last else np.maximum(out, 0.0)
    return out.reshape(np.shape(h_self)[:-1] + (w.shape[1],))


def tiny_batch(B=3, fanouts=(3, 2), D=4, seed=0):
    rng = np.random.default_rng(seed)
    feats, masks, shape = [], [], (B,)
    for li in range(len(fanouts) + 1):
        feats.append(rng.normal(size=shape + (D,)).astype(np.float32))
        masks.append(rng.random(shape) < 0.7)
        if li < len(fanouts):
            shape = shape + (fanouts[li],)
    masks[1][0] = False  # a seed with no valid neighbour
    return feats, masks


def test_attention_forward_by_hand():
    import jax.numpy as jnp

    B, D, H, C = 3, 4, 5, 6
    feats, masks = tiny_batch(B, (3, 2), D)
    shapes = refgnn.param_shapes(Attention, D, H, C, 2, ARGS)
    params = refgnn.init_params(shapes, 7)
    got = np.asarray(refgnn.forward(
        Attention, params, [jnp.asarray(f) for f in feats],
        [jnp.asarray(m) for m in masks], "highest", ARGS))

    def by_hand(weighted):
        h = list(feats)
        for li in range(2):
            p = params[f"layer{li}"]
            h = [hand_layer(li == 1, p, h[lev], h[lev + 1], masks[lev + 1],
                            ARGS["temperature"], weighted)
                 for lev in range(2 - li)]
        return h[0]

    want = by_hand(True)
    assert got.shape == (B, C)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the weights matter: the masked mean of the same projections differs
    assert np.abs(by_hand(False) - want).max() > 1e-2


def test_attention_param_shapes_last_layer_and_no_head():
    shapes = refgnn.param_shapes(Attention, 4, 5, 6, 3, ARGS)
    assert set(shapes) == {"layer0", "layer1", "layer2"}
    assert shapes["layer0"]["w"] == ((4, 5), "normal")
    assert shapes["layer1"]["w"] == ((5, 5), "normal")
    assert shapes["layer2"] == {"a": ((6,), "normal"), "b": ((6,), "zeros"),
                                "w": ((5, 6), "normal")}
    assert refgnn.leaf_paths(shapes)[:3] == [("layer0", "a"),
                                             ("layer0", "b"),
                                             ("layer0", "w")]


def test_attention_step_flops_by_hand():
    # B=2, fan-outs (3, 2), D=4, H=5, C=6; every level projects its own rows
    # and the rows of the level below
    # layer 0 on level 0: 2 rows over 6; proj 2*8*4*5 = 320, att 2*6*5 = 60
    l00 = (320 + 120) + (320 + 240)
    # layer 0 on level 1: 6 rows over 12; proj 2*18*4*5 = 720, att 120
    l01 = (720 + 240) + (720 + 480)
    # layer 1 (6 wide, input carries a gradient) on level 0: 2 rows over 6;
    # proj 2*8*5*6 = 480, att 2*6*6 = 72
    l10 = (480 + 144) + (480 + 288 + 480)
    assert counts.step_flops(Attention, 2, (3, 2), 4, 5, 6, ARGS) == \
        l00 + l01 + l10 == 5032


def test_model_args_reach_the_module(tmp_path):
    import jax.numpy as jnp

    # a required key: any call that dropped model_args would raise
    with pytest.raises(TypeError):
        refgnn.param_shapes(Attention, 4, 5, 6, 2)
    with pytest.raises(TypeError):
        counts.step_flops(Attention, 2, (3, 2), 4, 5, 6)
    feats, masks = tiny_batch()
    shapes = refgnn.param_shapes(Attention, 4, 5, 6, 2, ARGS)
    params = refgnn.init_params(shapes, 3)
    f = [jnp.asarray(x) for x in feats]
    m = [jnp.asarray(x) for x in masks]
    hot = refgnn.forward(Attention, params, f, m, "highest",
                         {"temperature": 0.25})
    cold = refgnn.forward(Attention, params, f, m, "highest", ARGS)
    assert np.abs(np.asarray(hot) - np.asarray(cold)).max() > 1e-3
    # and through a whole reference run: sampling, training step, AdamW
    indptr, indices = graphgen.powerlaw_csr(300, 5, 0.8, seed=2)
    X = graphgen.uniform_features(300, 4, 2)
    sampler = refgnn.Sampler(indptr, indices, np.arange(300), 8, (3, 2), 11)
    out = refgnn.run(Attention, OPT, shapes, X, sampler, 2, 6, 11, 2,
                     "highest", model_args=ARGS)
    assert all(np.isfinite(out["losses"]))
    assert all(np.abs(out["p"][k] - out["p0"][k]).max() > 0
               for k in out["paths"])


def test_gnn_config_passes_model_args_and_refuses_unknown(monkeypatch):
    import dataclasses

    import repro.models.gnn as gnn

    with open(os.path.join(BENCH, "configs", "gcn-products.json")) as f:
        cfg = json.load(f)
    assert "model_args" not in cfg
    assert harness.gnn_config(cfg) == gnn.GNNConfig(
        name=cfg["name"], model="gcn", feat_dim=100, hidden=256,
        n_classes=47, fanouts=(25, 10), batch_size=8000, lr=1e-3)
    with pytest.raises(TypeError):
        harness.gnn_config({**cfg, "model_args": {"heads": 4}})

    @dataclasses.dataclass(frozen=True)
    class WithHeads(gnn.GNNConfig):
        heads: int = 1

    monkeypatch.setattr(gnn, "GNNConfig", WithHeads)
    assert harness.gnn_config({**cfg, "model_args": {"heads": 4}}).heads == 4


# ---- sage and gcn through the interface, as before -----------------------

@pytest.mark.parametrize("name", ["sage", "gcn"])
def test_param_shapes_as_before(name):
    model = refgnn.load_model(name)
    new = refgnn.param_shapes(model, 4, 5, 6, 3)
    old = old_param_shapes(name, 4, 5, 6, 3)
    assert new == old
    assert refgnn.leaf_paths(new) == refgnn.leaf_paths(old)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", ["sage", "gcn"])
def test_run_bitwise_as_inline_mean(name, dtype, monkeypatch):
    model = refgnn.load_model(name)
    indptr, indices = graphgen.powerlaw_csr(400, 6, 0.8, seed=4)
    X = graphgen.uniform_features(400, 8, 4)
    shapes = refgnn.param_shapes(model, 8, 16, 5, 2)

    def run():
        sampler = refgnn.Sampler(indptr, indices, np.arange(400), 16,
                                 (5, 3), 2**31 + 9)
        return refgnn.run(model, OPT, shapes, X, sampler, 4, 5, 2**31 + 9,
                          3, "default", dtype=dtype)

    new = run()
    monkeypatch.setattr(refgnn, "forward", old_forward(name))
    old = run()
    assert new["losses"] == old["losses"]
    for part in ("g0", "p"):
        for k in new["paths"]:
            assert np.array_equal(new[part][k], old[part][k]), (part, k)


@pytest.mark.parametrize("config,flops", [("sage-papers100m", 63_698_944_000),
                                          ("gcn-products", 25_667_264_000)])
def test_step_flops_of_the_configs(config, flops):
    with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    assert counts.step_flops(refgnn.load_model(cfg["model"]),
                             cfg["batch_size"], cfg["fanouts"],
                             cfg["feat_dim"], cfg["hidden"],
                             cfg["n_classes"], cfg.get("model_args")) == flops
