"""Faults planted in the program's timed path, for the checks that the
comparison deciding ``correct`` catches them.  Each is a context manager
that patches one program function for its duration and forces the jitted
programs that inline it to trace again."""
from __future__ import annotations

import contextlib

import numpy as np


def _reset_finalize():
    import repro.train.batch as batch

    batch._fused_finalize = None


@contextlib.contextmanager
def _patched(module, name, value, retrace_finalize=False):
    old = getattr(module, name)
    setattr(module, name, value)
    if retrace_finalize:
        _reset_finalize()
    try:
        yield
    finally:
        setattr(module, name, old)
        if retrace_finalize:
            _reset_finalize()


def state_unchanged():
    """The train step returns the parameters it was given."""
    import repro.train.loop as loop

    return _patched(loop, "apply_updates", lambda params, updates: params)


def half_batch():
    """The loss is the mean over the first half of the batch only."""
    import repro.train.loop as loop

    real = loop.gnn_loss

    def half(cfg, params, batch, *a, **kw):
        n = batch["labels"].shape[0] // 2
        return real(cfg, params, {k: v[:n] for k, v in batch.items()},
                    *a, **kw)

    return _patched(loop, "gnn_loss", half)


def rows_altered(every: int = 16):
    """The fused gather drops (zeroes) every ``every``-th row it
    delivers, on both implementations of the kernel."""
    import repro.kernels.fused_batch as fb
    import repro.kernels.ref as ref

    real_p, real_x = fb.fused_gather_overlay_pallas, ref.fused_gather_overlay

    def spoil(out):
        return out.at[::every].set(0.0)

    stack = contextlib.ExitStack()
    stack.enter_context(_patched(
        fb, "fused_gather_overlay_pallas",
        lambda *a, **kw: spoil(real_p(*a, **kw)), retrace_finalize=True))
    stack.enter_context(_patched(
        ref, "fused_gather_overlay", lambda *a: spoil(real_x(*a)),
        retrace_finalize=True))
    return stack


def levels_altered(every: int = 16):
    """The sampler drops (sets to -1) every ``every``-th neighbour it
    returns, at every hop."""
    import repro.train.batch as batch

    real = batch.cache_sample_dispatch

    def dispatch(*args, **kw):
        resolve = real(*args, **kw)

        def spoiled(counter=None):
            levels, hits = resolve(counter=counter)
            out = [levels[0]]
            for lv in levels[1:]:
                drop = (np.arange(lv.size) % every == 0).reshape(lv.shape)
                out.append(np.where(drop, -1, lv))
            return out, hits

        return spoiled

    return _patched(batch, "cache_sample_dispatch", dispatch)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "rows_altered": rows_altered, "levels_altered": levels_altered}
