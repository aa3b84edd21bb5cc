"""Mamba2 SSD chunk-scan kernel (state-space duality).

Grid (batch*heads, chunks); the chunk axis is minor-most, so iterations
are sequential and the recurrent state (N, P) is carried in VMEM scratch:

  intra:  y_l += sum_{m<=l} exp(seg_l - seg_m) (C_l . B_m) x_m dt_m
  state:  S_c  = exp(seg_last) S_{c-1} + sum_m exp(seg_last - seg_m) B_m (x_m dt_m)^T
  inter:  y_l += exp(seg_l) C_l . S_{c-1}

Inputs are per-(b,h) chunk tiles: x (L, P), B/C (L, N), dA (L, 1).
TPU adaptation: the L x L decay/score matrix is built with MXU-friendly
dots; the state stays resident in VMEM across the whole sequence (one
HBM round-trip per chunk, vs. L for the naive recurrence).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import default_interpret as _default_interpret


def _ssd_kernel(x_ref, b_ref, c_ref, da_ref, y_ref, state_scr, *, chunk):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)      # (L, P)  (already x * dt)
    B = b_ref[0].astype(jnp.float32)      # (L, N)
    C = c_ref[0].astype(jnp.float32)      # (L, N)
    dA = da_ref[0].astype(jnp.float32)    # (L, 1)

    seg = jnp.cumsum(dA, axis=0)          # (L, 1) inclusive
    # ---- intra-chunk ----
    decay = seg - seg.T                   # (L, L): seg_l - seg_m
    l_idx = jax.lax.broadcasted_iota(jnp.int32, decay.shape, 0)
    m_idx = jax.lax.broadcasted_iota(jnp.int32, decay.shape, 1)
    att = jnp.where(m_idx <= l_idx, jnp.exp(decay), 0.0)
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    y = jax.lax.dot_general(cb * att, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (L, P)

    # ---- inter-chunk: contribution of the incoming state ----
    prev = state_scr[...]                 # (N, P)
    y += jnp.exp(seg) * jax.lax.dot_general(
        C, prev, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # ---- state update ----
    seg_last = seg[chunk - 1:chunk, :]    # (1, 1)
    w = jnp.exp(seg_last - seg)           # (L, 1)
    new_state = jnp.exp(seg_last) * prev + jax.lax.dot_general(
        B * w, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # (N, P)
    state_scr[...] = new_state

    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(xdt, Bh, Ch, dA, *, chunk: int = 64,
                    interpret: bool | None = None):
    """xdt: (BH, S, P) = x * dt; Bh/Ch: (BH, S, N); dA: (BH, S) (<= 0).
    Returns y: (BH, S, P).  Per-(batch, head) layout — the caller
    flattens (B, H) and broadcasts groups.  ``interpret=None`` resolves
    per backend (:func:`~.runtime.default_interpret`)."""
    if interpret is None:
        interpret = _default_interpret()
    bh, s, p = xdt.shape
    n = Bh.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    grid = (bh, s // chunk)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, p), xdt.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xdt, Bh, Ch, dA[..., None])
