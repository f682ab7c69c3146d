"""GCN's layer equation over a sampled neighbourhood (Kipf & Welling 2017,
as the Legion paper trains it: the self row averaged with the neighbour
mean), the plain reference for configurations with ``"model": "gcn"``.

    h' = relu(0.5 * (h_self + mean(h_neigh)) @ W + b)
"""
import jax
import jax.numpy as jnp

LAYER_PARAMS = {
    "b": (lambda d_in, d_out: (d_out,), "zeros"),
    "w": (lambda d_in, d_out: (d_in, d_out), "normal"),
}


def layer(p, h_self, h_agg, precision):
    x = 0.5 * (h_self + h_agg)
    return jax.nn.relu(jnp.matmul(x, p["w"].astype(x.dtype),
                                  precision=precision)
                       + p["b"].astype(x.dtype))


def layer_matmul_flops(rows: int, d_in: int, d_out: int) -> int:
    """Forward FLOPs of one layer's (rows, d_in) x (d_in, d_out) product."""
    return 2 * rows * d_in * d_out
