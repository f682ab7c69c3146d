"""The plain reference for the training cells: a host sampler, the GNN's
forward pass and loss in straightforward ``jax.numpy``, and AdamW.

It imports nothing of the program and takes nothing the program made.  It
follows the documented semantics that the program's first steps must
reproduce from the same seed:

* seeds: the training vertices in the order of a seeded permutation
  (``default_rng(plan_seed)``: the local tablet of a one-device clique),
  drawn with replacement by ``default_rng(seed)``, ``batch`` per step;
* sampling: per hop one ``integers(0, 2**31, (frontier, fanout))`` draw,
  neighbour ``indices[indptr[v] + r % deg(v)]``, -1 where ``v < 0`` or
  ``deg(v) == 0``;
* labels: the splitmix hash of the vertex id modulo the class count;
* layers: the equations of ``bench/reference/<model>.py``; the parameters
  initialised leaf by leaf from ``split(PRNGKey(seed), n_leaves)`` in
  sorted-key order, normal with standard deviation ``1/sqrt(fan_in)``,
  biases zero;
* optimizer: AdamW with global-norm gradient clipping.

A layer module ``bench/reference/<model>.py`` owns everything that differs
between models.  It provides four functions; each takes the layer's index
``li`` and the layer count ``n_layers`` first, and the configuration's
optional ``"model_args"`` object as keywords last:

* ``layer_params(li, n_layers, d_in, hidden, n_classes, **model_args)``
  -> ``(leaves, d_out)``: the layer's parameter leaves, name -> (shape,
  init), with the shape's first axis its fan-in and init ``"normal"`` or
  ``"zeros"``, and the width of the rows the layer outputs;
* ``has_head(**model_args)`` -> whether a linear ``head`` of shape
  ``(d_out of the last layer, n_classes)`` follows the last layer;
* ``layer(li, n_layers, p, h_self, h_neigh, mask, precision,
  **model_args)``: one level's new rows from its own rows ``h_self``
  ``(..., d_in)``, the rows of the level below ``h_neigh`` ``(..., f,
  d_in)`` and their validity ``mask`` ``(..., f)``; the aggregation is the
  module's (``masked_mean`` serves the mean aggregators);
* ``layer_flops(li, n_layers, rows, rows_below, d_in, d_out, grad_in,
  **model_args)`` -> ``(forward, backward)`` FLOPs of the layer on one
  level of ``rows`` rows over ``rows_below`` neighbour rows;
  ``grad_in`` says whether the layer's input carries a gradient
  (``benchlib.counts.step_flops`` sums these).

Precision is the configuration's: float32 arrays with its stated matmul
precision (``"default"`` on a TPU is one bfloat16 pass with float32
accumulation, which is what the program runs).  ``dtype="bfloat16"``
stores features, parameters at use and activations in bfloat16 at the same
matmul precision: the control that a correct cell must tell apart.
"""
from __future__ import annotations

import importlib.util
import math
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

REFERENCE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "reference")


def load_model(name: str):
    """The layer equation module ``bench/reference/<name>.py``."""
    path = os.path.join(REFERENCE_DIR, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---- data semantics -------------------------------------------------------

def splitmix_u32(x: np.ndarray, salt: int) -> np.ndarray:
    with np.errstate(over="ignore"):
        z = (x.astype(np.uint64)
             + np.uint64((0x9E3779B97F4A7C15 * (salt + 1)) & 0xFFFFFFFFFFFFFFFF))
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
    return (z & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def labels(ids: np.ndarray, graph_seed: int, n_classes: int) -> np.ndarray:
    h = splitmix_u32(np.asarray(ids, dtype=np.int64), graph_seed + 7)
    return (h % np.uint32(n_classes)).astype(np.int32)


def tablet(train_vertices: np.ndarray, plan_seed: int) -> np.ndarray:
    return train_vertices[np.random.default_rng(plan_seed).permutation(
        len(train_vertices))]


class Sampler:
    """The per-step seed draw and multi-hop sampling of one device."""

    def __init__(self, indptr, indices, tablet_ids, batch: int,
                 fanouts: Sequence[int], seed: int):
        self.indptr, self.indices = indptr, indices
        self.tablet = tablet_ids
        self.batch, self.fanouts = batch, tuple(fanouts)
        self.rng = np.random.default_rng(seed)

    def draw(self):
        """This step's random draws: (seeds, [hop draws])."""
        seeds = self.tablet[self.rng.integers(0, len(self.tablet),
                                              size=self.batch)]
        rands, n = [], self.batch
        for f in self.fanouts:
            rands.append(self.rng.integers(0, 1 << 31, size=(n, f)))
            n *= f
        return seeds, rands

    def levels(self, seeds, rands) -> List[np.ndarray]:
        out = [np.asarray(seeds, dtype=np.int64)]
        shape = (len(seeds),)
        for f, r in zip(self.fanouts, rands):
            v = out[-1].reshape(-1)
            ok = v >= 0
            sv = np.where(ok, v, 0)
            start = self.indptr[sv]
            deg = self.indptr[sv + 1] - start
            idx = start[:, None] + r % np.maximum(deg, 1)[:, None]
            nb = self.indices[np.minimum(idx, len(self.indices) - 1)]
            nb = np.where((ok & (deg > 0))[:, None], nb.astype(np.int64), -1)
            shape = shape + (f,)
            out.append(nb.reshape(shape))
        return out

    def step(self) -> List[np.ndarray]:
        return self.levels(*self.draw())


def unique_count(levels: Sequence[np.ndarray]) -> int:
    flat = np.concatenate([lv.reshape(-1) for lv in levels])
    return int(len(np.unique(flat[flat >= 0])))


def topo_requests(levels: Sequence[np.ndarray]) -> int:
    """Adjacency lists read: the valid sources of every sampled hop."""
    return int(sum((lv >= 0).sum() for lv in levels[:-1]))


# ---- model ----------------------------------------------------------------

def masked_mean(x, mask):
    """Mean of ``x`` (..., f, D) over its valid slots ``mask`` (..., f):
    the mean aggregators' aggregation, 0 where no slot is valid."""
    import jax.numpy as jnp

    m = mask.astype(x.dtype)[..., None]
    return (x * m).sum(axis=-2) / jnp.maximum(m.sum(axis=-2), 1.0)


def masked_mean_flops(rows_below: int, d: int) -> int:
    """``masked_mean``'s FLOPs over ``rows_below`` rows of width ``d``: one
    multiply and one add per element (its backward counts the same)."""
    return 2 * rows_below * d


def param_shapes(model, feat_dim: int, hidden: int, n_classes: int,
                 n_layers: int, model_args: Optional[Dict] = None) -> Dict:
    args = model_args or {}
    out, d_in = {}, feat_dim
    for li in range(n_layers):
        out[f"layer{li}"], d_in = model.layer_params(
            li, n_layers, d_in, hidden, n_classes, **args)
    if model.has_head(**args):
        out["head"] = ((d_in, n_classes), "normal")
    return out


def _leaves(shapes: Dict, prefix=()):
    """(path, (shape, init)) in sorted-key order."""
    for k in sorted(shapes):
        v = shapes[k]
        if isinstance(v, dict):
            yield from _leaves(v, prefix + (k,))
        else:
            yield prefix + (k,), v


def leaf_paths(shapes: Dict) -> List[tuple]:
    return [p for p, _ in _leaves(shapes)]


def init_params(shapes: Dict, seed: int) -> Dict:
    import jax
    import jax.numpy as jnp

    items = list(_leaves(shapes))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(items))
    out: Dict = {}
    for (path, (shape, init)), k in zip(items, keys):
        if init == "zeros":
            leaf = jnp.zeros(shape, jnp.float32)
        else:
            leaf = (jax.random.normal(k, shape, jnp.float32)
                    * (1.0 / math.sqrt(shape[0])))
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf
    return out


def forward(model, params, feats, masks, precision,
            model_args: Optional[Dict] = None):
    """feats[l]: (B, f1..fl, D); masks[l] (l >= 1): (B, f1..fl) -> logits."""
    import jax.numpy as jnp

    args = model_args or {}
    n_layers = len(feats) - 1
    h = list(feats)
    for li in range(n_layers):
        p = params[f"layer{li}"]
        h = [model.layer(li, n_layers, p, h[lev], h[lev + 1], masks[lev + 1],
                         precision, **args)
             for lev in range(n_layers - li)]
    if "head" not in params:
        return h[0]
    return jnp.matmul(h[0], params["head"].astype(h[0].dtype),
                      precision=precision)


def loss(model, params, feats, masks, y, precision,
         model_args: Optional[Dict] = None):
    import jax
    import jax.numpy as jnp

    logits = forward(model, params, feats, masks, precision,
                     model_args).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return (lse - ll).mean()


def make_step(model, opt: Dict, dtype: str, precision: str,
              model_args: Optional[Dict] = None):
    """One jitted training step of the reference:
    (params, m, v, count, feats, masks, y) -> (params, m, v, count, loss,
    clipped grads)."""
    import jax
    import jax.numpy as jnp

    cdt = jnp.dtype(dtype)
    lr, b1, b2 = opt["lr"], opt["b1"], opt["b2"]
    eps, wd, clip = opt["eps"], opt["weight_decay"], opt["grad_clip"]

    @jax.jit
    def step(params, m, v, count, feats, masks, y):
        feats = [f.astype(cdt) for f in feats]

        def f(p):
            pc = jax.tree.map(lambda x: x.astype(cdt), p)
            return loss(model, pc, feats, masks, y, precision, model_args)

        val, g = jax.value_and_grad(f)(params)
        g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g))
                         + 1e-12)
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / gnorm), g)
        count = count + 1
        c1 = 1.0 - b1 ** count.astype(jnp.float32)
        c2 = 1.0 - b2 ** count.astype(jnp.float32)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        params = jax.tree.map(
            lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps)
                                      + wd * p), params, m, v)
        return params, m, v, count, val, g

    return step


def batch_arrays(X: np.ndarray, levels: Sequence[np.ndarray], graph_seed: int,
                 n_classes: int):
    """Host feature rows (zero at padding), masks and labels of one batch."""
    feats, masks = [], []
    for li, lv in enumerate(levels):
        ok = lv >= 0
        f = X[np.where(ok, lv, 0).reshape(-1)].reshape(lv.shape + (X.shape[1],))
        f[~ok] = 0.0
        feats.append(f)
        masks.append(ok)
    return feats, masks, labels(levels[0], graph_seed, n_classes)


def run(model, opt: Dict, shapes: Dict, X: np.ndarray, sampler: Sampler,
        graph_seed: int, n_classes: int, seed: int, steps: int,
        precision: str, dtype: str = "float32", batches=None,
        keep: bool = False, model_args: Optional[Dict] = None) -> Dict:
    """``steps`` reference steps from ``seed``.  Returns the per-step
    losses, the first step's clipped gradient, the parameters before and
    after (each a dict of leaf path -> numpy array), and the sampled
    batches' unique-id and adjacency-read counts.  ``batches`` (host batch
    arrays per step, as ``keep=True`` returns them) skips sampling and
    gathering again; ``model_args`` go to the layer module."""
    import jax
    import jax.numpy as jnp

    params = init_params(shapes, seed)
    p0 = params
    zeros = jax.tree.map(jnp.zeros_like, params)
    m, v, count = zeros, zeros, jnp.zeros((), jnp.int32)
    step = make_step(model, opt, dtype, precision, model_args)
    losses, grad0, kept = [], None, []
    n_unique = n_topo = 0
    for i in range(steps):
        if batches is not None:
            b = batches[i]
        else:
            lv = sampler.step()
            n_unique += unique_count(lv)
            n_topo += topo_requests(lv)
            b = batch_arrays(X, lv, graph_seed, n_classes)
        if keep:
            kept.append(b)
        feats, masks, y = b
        params, m, v, count, val, g = step(
            params, m, v, count, [jnp.asarray(f) for f in feats],
            [jnp.asarray(k) for k in masks], jnp.asarray(y))
        del b, feats, masks
        losses.append(float(val))
        if i == 0:
            grad0 = g
    flat = {}
    paths = leaf_paths(shapes)
    for name, tree in (("p0", p0), ("p", params), ("g0", grad0)):
        flat[name] = {path: np.asarray(leaf) for path, leaf in zip(
            paths, jax.tree.leaves(tree))}
    return {"losses": losses, "p0": flat["p0"], "p": flat["p"],
            "g0": flat["g0"], "batches": kept, "paths": paths,
            "feature_requests": n_unique, "topo_requests": n_topo}
