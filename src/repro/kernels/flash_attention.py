"""Flash attention forward kernel (prefill hot path).

Grid (batch*heads, q_blocks, kv_blocks); the kv dim is the minor-most
grid axis, so iterations over it are sequential on TPU and the online-
softmax state (m, l, o accumulator) lives in VMEM scratch across them.
Causal masking by absolute positions; optional sliding window.

Block sizes are MXU-aligned (128 multiples) and sized so the working set
(q, k, v blocks + accumulator) stays a few MB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import default_interpret as _default_interpret

Q_BLOCK = 256
KV_BLOCK = 256
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, window, blk_q, blk_k, seq_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                      # (blk_q, d)
    k = k_ref[0]                      # (blk_k, d)
    v = v_ref[0]                      # (blk_k, dv)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _final():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "interpret", "blk_q", "blk_k"))
def flash_attention_pallas(q, k, v, *, window=None,
                           interpret: bool | None = None,
                           blk_q: int = Q_BLOCK, blk_k: int = KV_BLOCK):
    """q, k, v: (BH, S, d) — heads pre-flattened into the batch dim,
    grouped-query repetition done by the caller.  Causal.  Returns
    (BH, S, dv).  ``interpret=None`` resolves per backend
    (:func:`~.runtime.default_interpret`)."""
    if interpret is None:
        interpret = _default_interpret()
    bh, s, d = q.shape
    dv = v.shape[-1]
    blk_q = min(blk_q, s)
    blk_k = min(blk_k, s)
    assert s % blk_q == 0 and s % blk_k == 0, (s, blk_q, blk_k)
    scale = 1.0 / (d ** 0.5)
    grid = (bh, s // blk_q, s // blk_k)
    kernel = functools.partial(_flash_kernel, scale=scale, window=window,
                               blk_q=blk_q, blk_k=blk_k, seq_len=s)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
