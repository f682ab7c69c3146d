"""Operations and bytes of the work a training step needs, from shapes.

``step_flops`` counts the multiply-adds of the GNN's forward pass and the
backward pass that training needs: every weight gradient, and the input
gradient of every layer whose input carries one (the first layer's inputs
are feature rows, which carry none).  Each layer module counts its own
layer, aggregation included (``layer_flops``; the masked-mean aggregations
count one multiply and one add per element, and their backward the same
where their input carries a gradient); the linear head, where the module
has one, is counted here.  Bias adds, activations and the loss are left
out.  Nothing is counted twice for recomputation.

``fused_gather_bytes`` is the HBM traffic of one call of the fused
gather-and-overlay kernel: every real row is read once from the cache
table or the staged miss block, every output row (padding included) is
written once, and both int32 index maps are read.
"""
from __future__ import annotations

from typing import Optional, Sequence


def level_rows(batch: int, fanouts: Sequence[int]):
    """Rows of each sampled level: B, B*f1, B*f1*f2, ..."""
    rows, n = [], batch
    rows.append(n)
    for f in fanouts:
        n *= f
        rows.append(n)
    return rows


def step_flops(model, batch: int, fanouts: Sequence[int], feat_dim: int,
               hidden: int, n_classes: int,
               model_args: Optional[dict] = None) -> int:
    """Forward plus backward FLOPs of one training step.  ``model`` is the
    layer module (``layer_params``, ``layer_flops``, ``has_head``)."""
    args = model_args or {}
    rows = level_rows(batch, fanouts)
    n_layers = len(fanouts)
    total = 0
    d_in = feat_dim
    for li in range(n_layers):
        _, d_out = model.layer_params(li, n_layers, d_in, hidden, n_classes,
                                      **args)
        grad_in = li > 0  # layer 0 reads features, which carry no gradient
        for lev in range(n_layers - li):
            total += sum(model.layer_flops(li, n_layers, rows[lev],
                                           rows[lev + 1], d_in, d_out,
                                           grad_in, **args))
        d_in = d_out
    if model.has_head(**args):
        head = 2 * batch * d_in * n_classes
        total += 3 * head  # forward, weight and input gradients
    return total


def fused_gather_bytes(n_pad: int, n_rows: int, width: int,
                       itemsize: int = 4) -> int:
    """One fused gather-and-overlay call over ``n_pad`` output rows, of
    which ``n_rows`` are real, at ``width`` lanes of ``itemsize`` bytes."""
    return (n_rows + n_pad) * width * itemsize + 2 * n_pad * 4
