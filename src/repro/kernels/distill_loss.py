"""Fused distillation loss kernels (eq. 3 / 5).

Per sample i with logits z_i (C classes), label y_i and KD target row
g_i (the G_out row of y_i's ground truth):

  phi_i = logsumexp(z_i) - z_i[y_i]
  psi_i = sum_c g_ic * (logsumexp(z_i) - z_ic)
  out_i = phi_i + beta * psi_i

One VMEM pass per (row-block x full class dim): max, exp-sum, label pick
and KD dot all fused — the server's output-to-model conversion (eq. 5)
runs this over every seed sample for K_s iterations.

Two entry points:

* :func:`distill_loss_pallas` — the original fused ``phi + beta * psi``
  (forward only; assumes rows of g sum to 1, as G_out rows do).
* :func:`distill_phi_psi` — per-sample (phi, psi) with a ``custom_vjp``
  whose backward pass is a second fused kernel, so the *device-side*
  local-SGD hot path (``core.losses.fd_loss`` under ``value_and_grad``
  inside the round loop's scan) runs both directions through Pallas.
  psi here carries the exact ``sum(g) * lse`` term, so it matches
  ``kd_regularizer`` even for unnormalised / zero G_out rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .runtime import default_interpret as _default_interpret

ROW_BLOCK = 128


def _distill_kernel(z_ref, y_ref, g_ref, beta_ref, o_ref):
    z = z_ref[...].astype(jnp.float32)          # (R, C)
    y = y_ref[...]                              # (R, 1) int32
    g = g_ref[...].astype(jnp.float32)          # (R, C)
    beta = beta_ref[0, 0]
    m = jnp.max(z, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(z - m), axis=-1, keepdims=True)) + m
    onehot = (jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) == y)
    zy = jnp.sum(jnp.where(onehot, z, 0.0), axis=-1, keepdims=True)
    gz = jnp.sum(g * z, axis=-1, keepdims=True)
    phi = lse - zy
    psi = lse - gz
    o_ref[...] = (phi + beta * psi).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def distill_loss_pallas(logits, labels, g_rows, beta, *,
                        interpret: bool | None = None):
    """logits: (N, C); labels: (N,) int32; g_rows: (N, C) KD target rows;
    beta: scalar. Returns per-sample losses (N,).  ``interpret=None``
    resolves per backend (:func:`~.runtime.default_interpret`)."""
    if interpret is None:
        interpret = _default_interpret()
    n, c = logits.shape
    rb = min(ROW_BLOCK, n)
    if n % rb:
        pad = -(-n // rb) * rb - n
        logits = jnp.pad(logits, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad))
        g_rows = jnp.pad(g_rows, ((0, pad), (0, 0)))
    beta_arr = jnp.full((1, 1), beta, jnp.float32)
    out = pl.pallas_call(
        _distill_kernel,
        grid=(logits.shape[0] // rb,),
        in_specs=[
            pl.BlockSpec((rb, c), lambda i: (i, 0)),
            pl.BlockSpec((rb, 1), lambda i: (i, 0)),
            pl.BlockSpec((rb, c), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((logits.shape[0], 1), jnp.float32),
        interpret=interpret,
    )(logits, labels[:, None].astype(jnp.int32), g_rows, beta_arr)
    return out[:n, 0]


# ---------------------------------------------------------------------------
# custom_vjp pair: per-sample (phi, psi) with a fused backward kernel
# ---------------------------------------------------------------------------

def _phi_psi_kernel(z_ref, y_ref, g_ref, phi_ref, psi_ref):
    z = z_ref[...].astype(jnp.float32)          # (R, C)
    y = y_ref[...]                              # (R, 1) int32
    g = g_ref[...].astype(jnp.float32)          # (R, C)
    m = jnp.max(z, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(z - m), axis=-1, keepdims=True)) + m
    onehot = (jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) == y)
    zy = jnp.sum(jnp.where(onehot, z, 0.0), axis=-1, keepdims=True)
    sg = jnp.sum(g, axis=-1, keepdims=True)     # G_out rows may be unnorm.
    gz = jnp.sum(g * z, axis=-1, keepdims=True)
    phi_ref[...] = (lse - zy).astype(phi_ref.dtype)
    psi_ref[...] = (sg * lse - gz).astype(psi_ref.dtype)


def _phi_psi_bwd_kernel(z_ref, y_ref, g_ref, dphi_ref, dpsi_ref,
                        dz_ref, dg_ref):
    z = z_ref[...].astype(jnp.float32)
    y = y_ref[...]
    g = g_ref[...].astype(jnp.float32)
    dphi = dphi_ref[...].astype(jnp.float32)    # (R, 1)
    dpsi = dpsi_ref[...].astype(jnp.float32)
    m = jnp.max(z, axis=-1, keepdims=True)
    e = jnp.exp(z - m)
    lse = jnp.log(jnp.sum(e, axis=-1, keepdims=True)) + m
    p = e / jnp.sum(e, axis=-1, keepdims=True)  # softmax rows
    onehot = (jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) == y)
    sg = jnp.sum(g, axis=-1, keepdims=True)
    # d phi / dz = p - onehot;  d psi / dz = sum(g) * p - g
    dz_ref[...] = (dphi * (p - jnp.where(onehot, 1.0, 0.0)) +
                   dpsi * (sg * p - g)).astype(dz_ref.dtype)
    # d psi / dg = lse - z (phi does not touch g)
    dg_ref[...] = (dpsi * (lse - z)).astype(dg_ref.dtype)


def _pad_rows(n, rb, *arrs):
    pad = -(-n // rb) * rb - n
    if pad == 0:
        return arrs
    return tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                 for a in arrs)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _phi_psi_fwd_call(logits, labels, g_rows, interpret: bool):
    n, c = logits.shape
    rb = min(ROW_BLOCK, n)
    y2 = labels[:, None].astype(jnp.int32)
    logits, y2, g_rows = _pad_rows(n, rb, logits, y2, g_rows)
    spec_c = pl.BlockSpec((rb, c), lambda i: (i, 0))
    spec_1 = pl.BlockSpec((rb, 1), lambda i: (i, 0))
    phi, psi = pl.pallas_call(
        _phi_psi_kernel,
        grid=(logits.shape[0] // rb,),
        in_specs=[spec_c, spec_1, spec_c],
        out_specs=[spec_1, spec_1],
        out_shape=[jax.ShapeDtypeStruct((logits.shape[0], 1), jnp.float32)] * 2,
        interpret=interpret,
    )(logits, y2, g_rows)
    return phi[:n, 0], psi[:n, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _phi_psi_bwd_call(logits, labels, g_rows, dphi, dpsi, interpret: bool):
    n, c = logits.shape
    rb = min(ROW_BLOCK, n)
    y2 = labels[:, None].astype(jnp.int32)
    logits, y2, g_rows, dphi2, dpsi2 = _pad_rows(
        n, rb, logits, y2, g_rows, dphi[:, None], dpsi[:, None])
    spec_c = pl.BlockSpec((rb, c), lambda i: (i, 0))
    spec_1 = pl.BlockSpec((rb, 1), lambda i: (i, 0))
    dz, dg = pl.pallas_call(
        _phi_psi_bwd_kernel,
        grid=(logits.shape[0] // rb,),
        in_specs=[spec_c, spec_1, spec_c, spec_1, spec_1],
        out_specs=[spec_c, spec_c],
        out_shape=[jax.ShapeDtypeStruct(logits.shape, jnp.float32)] * 2,
        interpret=interpret,
    )(logits, y2, g_rows, dphi2, dpsi2)
    return dz[:n], dg[:n]


@jax.custom_vjp
def distill_phi_psi(logits, labels, g_rows):
    """Per-sample (phi, psi): logits (N, C); labels (N,) int; g_rows (N, C)
    KD target rows.  Forward *and* backward run as fused Pallas kernels
    (interpret off-TPU), differentiable in logits and g_rows."""
    return _phi_psi_fwd_call(logits, labels, g_rows,
                             interpret=_default_interpret())


def _distill_phi_psi_fwd(logits, labels, g_rows):
    out = _phi_psi_fwd_call(logits, labels, g_rows,
                            interpret=_default_interpret())
    return out, (logits, labels, g_rows)


def _distill_phi_psi_bwd(res, cts):
    logits, labels, g_rows = res
    dphi, dpsi = cts
    dz, dg = _phi_psi_bwd_call(logits, labels, g_rows, dphi, dpsi,
                               interpret=_default_interpret())
    return (dz.astype(logits.dtype),
            np.zeros(labels.shape, jax.dtypes.float0),
            dg.astype(g_rows.dtype))


distill_phi_psi.defvjp(_distill_phi_psi_fwd, _distill_phi_psi_bwd)
