"""Blocked causal flash attention (forward) — the LM-serving/prefill hot spot.

Standard online-softmax tiling for the MXU: grid (BH, n_q_blocks,
n_kv_blocks) with the KV dim innermost; running (m, l, acc) live in VMEM
scratch across the KV sweep and the output block is written on the last KV
step.  Causal blocks above the diagonal are masked (the wrapper still
iterates them; skipping via a lower-triangular grid is a perf iteration
recorded in EXPERIMENTS.md §Perf).

Block sizes default to (128, 128): MXU-aligned (128 lanes) and small enough
that q/k/v/acc blocks fit VMEM for Dh <= 256:
  VMEM ≈ (bq + 2*bk) * Dh * 2B + bq * Dh * 4B + O(bq*bk) ≈ 0.4 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, causal: bool, scale: float,
                  n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
    k = k_ref[0].astype(jnp.float32)  # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, block_q: int = 128,
                           block_k: int = 128, interpret: bool = None):
    """q/k/v: (BH, S, Dh) with heads pre-flattened into the batch dim."""
    if interpret is None:
        interpret = interpret_default()
    BH, Sq, Dh = q.shape
    Sk = k.shape[1]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    n_q, n_kv = Sq // block_q, Sk // block_k
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, causal=causal,
        scale=Dh ** -0.5, n_kv=n_kv)
    fn = pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, Dh), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, Dh), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, Dh), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dh), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Dh), jnp.float32),
        ],
        interpret=interpret,
    )
    return fn(q, k, v)
