"""GraphSAGE's layer equation (Hamilton et al. 2017, mean aggregator), the
plain reference for configurations with ``"model": "sage"``; the layer
module interface is described in ``benchlib/refgnn.py``.

    h' = relu(h_self @ W_self + mean(h_neigh) @ W_neigh + b)

Every layer is ``hidden`` wide and a linear head follows the last.
"""
import jax
import jax.numpy as jnp

from benchlib.refgnn import masked_mean, masked_mean_flops


def layer_params(li, n_layers, d_in, hidden, n_classes):
    return {"b": ((hidden,), "zeros"),
            "w_neigh": ((d_in, hidden), "normal"),
            "w_self": ((d_in, hidden), "normal")}, hidden


def has_head():
    return True


def layer(li, n_layers, p, h_self, h_neigh, mask, precision):
    def dot(x, w):
        return jnp.matmul(x, w.astype(x.dtype), precision=precision)

    h_agg = masked_mean(h_neigh, mask)
    return jax.nn.relu(dot(h_self, p["w_self"]) + dot(h_agg, p["w_neigh"])
                       + p["b"].astype(h_self.dtype))


def layer_flops(li, n_layers, rows, rows_below, d_in, d_out, grad_in):
    """Two (rows, d_in) x (d_in, d_out) products after the mean over the
    level below; backward: both weight gradients, and where the input
    carries a gradient, the input gradients through the mean."""
    mm = 2 * (2 * rows * d_in * d_out)
    agg = masked_mean_flops(rows_below, d_in)
    return mm + agg, mm + (mm + agg if grad_in else 0)
