"""GAT's layer equation (Veličković et al. 2018, arXiv 1710.10903) as PyG's
``examples/ogbn_products_gat.py`` stacks it, the plain reference for
configurations with ``"model": "gat"``; the layer module interface is
described in ``benchlib/refgnn.py``.

For ``H`` heads of width ``C`` (``hidden`` on hidden layers, ``n_classes``
on the last), with ``W`` shared by a level's own rows and the rows below:

    z_s = h_self @ W,  z_j = h_neigh_j @ W             (..., H, C)
    e_j    = leaky_relu(a_src . z_j + a_dst . z_s)     valid slots j
    e_self = leaky_relu(a_src . z_s + a_dst . z_s)     the self loop
    alpha  = softmax over {valid j} and the self loop
    agg    = alpha_self z_s + sum_j alpha_j z_j
    hidden layer: elu(concat_H(agg) + b + h_self @ W_skip + b_skip)
    last layer:   mean_H(agg) + b + h_self @ W_skip + b_skip     (logits)

No head follows the last layer.  Departures from PyG's recipe:

* no dropout (PyG drops 0.5 of the features between layers; none on the
  attention), as in the other cells;
* the sampler draws neighbours with replacement (``r % deg``) and each
  sampled slot has its own subtree (the padded tree), where PyG's
  ``NeighborSampler`` deduplicates each hop into a bipartite graph;
* the optimizer is the benchmark's AdamW (weight decay 0.01, clipping
  1.0) where PyG runs plain Adam at the same learning rate.
"""
import jax
import jax.numpy as jnp

SLOPE = 0.2  # LeakyReLU slope of the scores (the paper's, PyG's default)


def _widths(li, n_layers, hidden, n_classes, n_heads):
    """(per-head width C, output width) of layer ``li``."""
    if li == n_layers - 1:
        return n_classes, n_classes
    return hidden, n_heads * hidden


def layer_params(li, n_layers, d_in, hidden, n_classes, *, n_heads):
    c, d_out = _widths(li, n_layers, hidden, n_classes, n_heads)
    return {"att_dst": ((c, n_heads), "normal"),
            "att_src": ((c, n_heads), "normal"),
            "b": ((d_out,), "zeros"),
            "b_skip": ((d_out,), "zeros"),
            "w": ((d_in, n_heads * c), "normal"),
            "w_skip": ((d_in, d_out), "normal")}, d_out


def has_head(*, n_heads):
    return False


def layer(li, n_layers, p, h_self, h_neigh, mask, precision, *, n_heads):
    def dot(x, w):
        return jnp.matmul(x, w.astype(x.dtype), precision=precision)

    dt = h_self.dtype
    z_s = dot(h_self, p["w"])
    z_j = dot(h_neigh, p["w"])
    skip = dot(h_self, p["w_skip"]) + p["b_skip"].astype(dt)
    z_s = z_s.reshape(z_s.shape[:-1] + (n_heads, -1))
    z_j = z_j.reshape(z_j.shape[:-1] + (n_heads, -1))
    a_src, a_dst = p["att_src"].T.astype(dt), p["att_dst"].T.astype(dt)
    s_dst = (z_s * a_dst).sum(axis=-1)
    e_self = jax.nn.leaky_relu((z_s * a_src).sum(axis=-1) + s_dst, SLOPE)
    e_j = jax.nn.leaky_relu((z_j * a_src).sum(axis=-1) + s_dst[..., None, :],
                            SLOPE)
    e_j = jnp.where(mask[..., None], e_j, -jnp.inf)
    m = jax.lax.stop_gradient(jnp.maximum(e_self, e_j.max(axis=-2)))
    p_self = jnp.exp(e_self - m)
    p_j = jnp.exp(e_j - m[..., None, :])
    denom = p_self + p_j.sum(axis=-2)
    agg = ((p_self[..., None] * z_s + (p_j[..., None] * z_j).sum(axis=-3))
           / denom[..., None])
    if li == n_layers - 1:
        return agg.mean(axis=-2) + p["b"].astype(dt) + skip
    return jax.nn.elu(agg.reshape(agg.shape[:-2] + (-1,)) + p["b"].astype(dt)
                      + skip)


def layer_flops(li, n_layers, rows, rows_below, d_in, d_out, grad_in, *,
                n_heads):
    """Forward: the projections of the level's rows and the rows below
    (``n_heads * C`` wide), the skip, the source score of every slot and of
    the self loop, the target score of each row, and the weighted sum over
    the slots and the self loop.  Backward: the weight gradients of the
    three products; the scores and the weighted sum twice over (their
    vectors' and the projections' gradients); and where the input carries
    a gradient, the input gradients of the three products."""
    hc = d_out if li < n_layers - 1 else n_heads * d_out
    proj = 2 * (rows + rows_below) * d_in * hc + 2 * rows * d_in * d_out
    score = 2 * (rows_below + rows) * hc + 2 * rows * hc
    wsum = 2 * (rows_below + rows) * hc
    return proj + score + wsum, proj + (proj if grad_in else 0) \
        + 2 * (score + wsum)
