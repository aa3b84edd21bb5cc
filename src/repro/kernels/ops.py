"""Jit'd public wrappers for the Pallas kernels.

Every kernel resolves ``interpret`` per backend
(:func:`.runtime.default_interpret`): on a TPU the pallas_call lowers to a
compiled Mosaic kernel, on the CPU (the test suite runs with
``JAX_PLATFORMS=cpu``) it runs in the Pallas interpreter.
"""
from __future__ import annotations

import jax.numpy as jnp

from .distill_loss import distill_loss_pallas
from .flash_attention import flash_attention_pallas
from .mixup_kernel import mixup_pallas
from .ssd_scan import ssd_scan_pallas


def mixup(a, b, lam: float):
    """eq. (6): lam * a + (1 - lam) * b over a batch of flattened samples."""
    n = a.shape[0]
    flat_a = a.reshape(n, -1)
    flat_b = b.reshape(n, -1)
    la = jnp.full((n,), lam, jnp.float32)
    lb = jnp.full((n,), 1.0 - lam, jnp.float32)
    out = mixup_pallas(flat_a, flat_b, la, lb)
    return out.reshape(a.shape)


def inverse_mixup_pair(mixed_a, mixed_b, lam: float):
    """eq. (7), N=2: returns the two hard-labelled unmixed samples."""
    lam_hat = lam / (2.0 * lam - 1.0)
    n = mixed_a.shape[0]
    fa = mixed_a.reshape(n, -1)
    fb = mixed_b.reshape(n, -1)
    l1 = jnp.full((n,), lam_hat, jnp.float32)
    l2 = 1.0 - l1
    s1 = mixup_pallas(fa, fb, l1, l2)
    s2 = mixup_pallas(fa, fb, l2, l1)
    return s1.reshape(mixed_a.shape), s2.reshape(mixed_a.shape)


def distill_loss(logits, labels, gout, beta: float):
    """Mean of eq. (3) over a batch; gout: (C, C) KD table."""
    g_rows = gout[labels]
    per = distill_loss_pallas(logits, labels, g_rows, beta)
    return jnp.mean(per)


def flash_attention(q, k, v, *, window=None):
    """Causal attention, (BH, S, d) layout (see kernels/flash_attention)."""
    return flash_attention_pallas(q, k, v, window=window)


def ssd_scan(xdt, Bh, Ch, dA, *, chunk: int = 64):
    """Mamba2 SSD over (BH, S, ·) tensors."""
    return ssd_scan_pallas(xdt, Bh, Ch, dA, chunk=chunk)
