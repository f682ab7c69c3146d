"""GraphSAGE, GCN and GAT over fixed-fanout sampled subgraphs (paper §2,
§6.1).

The sampled mini-batch is the padded tensor form of the paper's 2-hop
25x10 GraphSAGE workflow (Figure 1):

  feats[0] (B, D)          seed features
  feats[1] (B, f1, D)      hop-1 neighbor features
  feats[2] (B, f1, f2, D)  hop-2 neighbor features
  mask[l]  same shape minus D  (False = padded / zero-degree slot)

AGGREGATE = masked mean; UPDATE = W_self h + W_neigh a  (SAGE) or
W (mean(h ∪ N(h)))  (GCN); hidden dim 256, 2 layers as in the paper.

GAT (Veličković et al. 2018, as PyG's ``GATConv`` with its self loop and a
skip linear per layer) replaces the mean with per-head attention of each
row over its valid sampled slots and itself; hidden layers concatenate
their ``n_heads`` heads of ``hidden`` and apply ELU, the last averages its
heads into the classes, and no separate head follows.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.params import Def
from repro.models.sharding import Distribution

_GAT_SLOPE = 0.2  # LeakyReLU slope of GAT's scores (paper and PyG default)


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    name: str = "graphsage"
    model: str = "sage"  # sage | gcn | gat
    feat_dim: int = 128
    hidden: int = 256
    n_classes: int = 32
    fanouts: tuple = (25, 10)
    batch_size: int = 8000
    lr: float = 1e-3
    n_heads: int = 1  # gat: attention heads per layer


def _gat_defs(cfg: GNNConfig) -> dict:
    """Per layer: the projection ``w`` shared by source and target rows,
    the two score vectors (first axis ``C``, the fan-in of their dot
    product), the bias and the skip linear.  Per-head width ``C`` is
    ``hidden`` on hidden layers and ``n_classes`` on the last."""
    L, H = len(cfg.fanouts), cfg.n_heads
    out = {}
    d_in = cfg.feat_dim
    for l in range(L):
        last = l == L - 1
        C = cfg.n_classes if last else cfg.hidden
        d_out = C if last else H * C
        out[f"layer{l}"] = {
            "w": Def((d_in, H * C), ("embed", "ff")),
            "att_src": Def((C, H), (None, None)),
            "att_dst": Def((C, H), (None, None)),
            "b": Def((d_out,), ("ff",), init="zeros"),
            "w_skip": Def((d_in, d_out), ("embed", "ff")),
            "b_skip": Def((d_out,), ("ff",), init="zeros"),
        }
        d_in = d_out
    return out


def defs(cfg: GNNConfig) -> dict:
    if cfg.model == "gat":
        return _gat_defs(cfg)
    L = len(cfg.fanouts)
    out = {}
    d_in = cfg.feat_dim
    for l in range(L):
        d_out = cfg.hidden
        if cfg.model == "sage":
            out[f"layer{l}"] = {
                "w_self": Def((d_in, d_out), ("embed", "ff")),
                "w_neigh": Def((d_in, d_out), ("embed", "ff")),
                "b": Def((d_out,), ("ff",), init="zeros"),
            }
        else:  # gcn
            out[f"layer{l}"] = {
                "w": Def((d_in, d_out), ("embed", "ff")),
                "b": Def((d_out,), ("ff",), init="zeros"),
            }
        d_in = d_out
    out["head"] = Def((d_in, cfg.n_classes), ("ff", None))
    return out


def masked_mean(x: jax.Array, mask: jax.Array) -> jax.Array:
    """Mean over the second-to-last axis with a validity mask."""
    m = mask.astype(x.dtype)[..., None]
    s = (x * m).sum(axis=-2)
    c = jnp.maximum(m.sum(axis=-2), 1.0)
    return s / c


def _apply_layer(cfg: GNNConfig, p: dict, h_self: jax.Array, h_agg: jax.Array):
    if cfg.model == "sage":
        out = h_self @ p["w_self"] + h_agg @ p["w_neigh"] + p["b"]
    else:
        out = 0.5 * (h_self + h_agg) @ p["w"] + p["b"]
    return jax.nn.relu(out)


def _gat_layer(cfg: GNNConfig, p: dict, last: bool, h_self: jax.Array,
               h_nb: jax.Array, mask: jax.Array) -> jax.Array:
    """One level of a GAT layer: ``h_self`` (..., d), ``h_nb`` (..., f, d)
    and its ``mask`` (..., f).  The softmax runs over the valid slots and
    the self loop, shifted by their (constant) maximum as PyG does."""
    H = cfg.n_heads
    with jax.named_scope("gat_project"):
        z_s = h_self @ p["w"]
        z_j = h_nb @ p["w"]
        skip = h_self @ p["w_skip"] + p["b_skip"]
    with jax.named_scope("gat_attention"):
        z_s = z_s.reshape(z_s.shape[:-1] + (H, -1))  # (..., H, C)
        z_j = z_j.reshape(z_j.shape[:-1] + (H, -1))  # (..., f, H, C)
        a_src, a_dst = p["att_src"].T, p["att_dst"].T  # (H, C)
        s_dst = (z_s * a_dst).sum(axis=-1)
        e_self = jax.nn.leaky_relu((z_s * a_src).sum(axis=-1) + s_dst,
                                   _GAT_SLOPE)
        e_j = jax.nn.leaky_relu((z_j * a_src).sum(axis=-1)
                                + s_dst[..., None, :], _GAT_SLOPE)
        e_j = jnp.where(mask[..., None], e_j, -jnp.inf)
        m = jax.lax.stop_gradient(jnp.maximum(e_self, e_j.max(axis=-2)))
        p_self = jnp.exp(e_self - m)
        p_j = jnp.exp(e_j - m[..., None, :])
        denom = p_self + p_j.sum(axis=-2)
        agg = ((p_self[..., None] * z_s
                + (p_j[..., None] * z_j).sum(axis=-3)) / denom[..., None])
    if last:
        return agg.mean(axis=-2) + p["b"] + skip
    return jax.nn.elu(agg.reshape(agg.shape[:-2] + (-1,)) + p["b"] + skip)


def forward(cfg: GNNConfig, params: dict, batch: dict,
            dist: Distribution = None) -> jax.Array:
    """batch: feats_0..feats_L, mask_1..mask_L -> logits (B, n_classes)."""
    L = len(cfg.fanouts)
    h = [batch[f"feats_{l}"] for l in range(L + 1)]
    for l in range(L):
        p = params[f"layer{l}"]
        new_h = []
        for lev in range(L - l):
            mask = batch[f"mask_{lev + 1}"]
            if cfg.model == "gat":
                new_h.append(_gat_layer(cfg, p, l == L - 1, h[lev],
                                        h[lev + 1], mask))
            else:
                agg = masked_mean(h[lev + 1], mask)
                new_h.append(_apply_layer(cfg, p, h[lev], agg))
        h = new_h
    if cfg.model == "gat":
        return h[0]
    return h[0] @ params["head"]


def loss_fn(cfg: GNNConfig, params: dict, batch: dict,
            dist: Distribution = None):
    logits = forward(cfg, params, batch, dist).astype(jnp.float32)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    loss = (lse - ll).mean()
    acc = (logits.argmax(-1) == labels).mean()
    return loss, {"acc": acc}
