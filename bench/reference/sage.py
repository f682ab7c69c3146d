"""GraphSAGE's layer equation (Hamilton et al. 2017, mean aggregator), the
plain reference for configurations with ``"model": "sage"``.

    h' = relu(h_self @ W_self + mean(h_neigh) @ W_neigh + b)
"""
import jax
import jax.numpy as jnp

# parameter leaves of one layer: name -> (shape as a function of (d_in,
# d_out), init); fan-in is the shape's first axis
LAYER_PARAMS = {
    "b": (lambda d_in, d_out: (d_out,), "zeros"),
    "w_neigh": (lambda d_in, d_out: (d_in, d_out), "normal"),
    "w_self": (lambda d_in, d_out: (d_in, d_out), "normal"),
}


def layer(p, h_self, h_agg, precision):
    def dot(x, w):
        return jnp.matmul(x, w.astype(x.dtype), precision=precision)

    return jax.nn.relu(dot(h_self, p["w_self"]) + dot(h_agg, p["w_neigh"])
                       + p["b"].astype(h_self.dtype))


def layer_matmul_flops(rows: int, d_in: int, d_out: int) -> int:
    """Forward multiply-add FLOPs of one layer applied to ``rows`` rows:
    two (rows, d_in) x (d_in, d_out) products."""
    return 2 * (2 * rows * d_in * d_out)
