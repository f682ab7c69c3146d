"""GCN's layer equation over a sampled neighbourhood (Kipf & Welling 2017,
as the Legion paper trains it: the self row averaged with the neighbour
mean), the plain reference for configurations with ``"model": "gcn"``; the
layer module interface is described in ``benchlib/refgnn.py``.

    h' = relu(0.5 * (h_self + mean(h_neigh)) @ W + b)

Every layer is ``hidden`` wide and a linear head follows the last.
"""
import jax
import jax.numpy as jnp

from benchlib.refgnn import masked_mean, masked_mean_flops


def layer_params(li, n_layers, d_in, hidden, n_classes):
    return {"b": ((hidden,), "zeros"),
            "w": ((d_in, hidden), "normal")}, hidden


def has_head():
    return True


def layer(li, n_layers, p, h_self, h_neigh, mask, precision):
    x = 0.5 * (h_self + masked_mean(h_neigh, mask))
    return jax.nn.relu(jnp.matmul(x, p["w"].astype(x.dtype),
                                  precision=precision)
                       + p["b"].astype(x.dtype))


def layer_flops(li, n_layers, rows, rows_below, d_in, d_out, grad_in):
    """One (rows, d_in) x (d_in, d_out) product after the mean over the
    level below; backward: the weight gradient, and where the input
    carries a gradient, the input gradients through the mean."""
    mm = 2 * rows * d_in * d_out
    agg = masked_mean_flops(rows_below, d_in)
    return mm + agg, mm + (mm + agg if grad_in else 0)
