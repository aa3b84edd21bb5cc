"""Server-side output-to-model conversion (eq. 5, Algorithm 1 line 10).

The server transfers the knowledge in the global average output vectors
G_out into the global model by running K_s SGD-with-KD iterations over the
collected (and for Mix2FLD, inversely mixed-up) seed samples.

Two entry points share the same per-step math:

* :func:`output_to_model` — the single-config path (static ``iters``)
  used by ``FederatedTrainer.run``.  ``key`` is **required**: the old
  silent ``PRNGKey(0)`` default made every caller that omitted it draw
  identical batch sequences across rounds and configs.
* :func:`output_to_model_steps` — the grid path for the protocol-sweep
  engine: the scan length is the grid-wide maximum ``max(iters)`` and a
  per-config ``iters`` mask turns trailing steps into no-ops, so configs
  with different conversion budgets share one compiled scan.  The step
  keys are precomputed host-side (``jax.random.split`` is not
  prefix-stable across different split counts), which keeps every live
  step bitwise-equal to the single-config path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .losses import cross_entropy, kd_regularizer


def _conversion_step(model_apply, seeds_x, seeds_y, gout, n_train, batch,
                     eta, beta, params, key):
    """One eq. (5) SGD-with-KD step shared by both conversion paths.
    Returns (updated params, loss)."""
    hard = seeds_y.ndim == 1
    idx = jax.random.randint(key, (batch,), 0, n_train)
    xb, yb = seeds_x[idx], seeds_y[idx]

    def loss_fn(p_):
        logits = model_apply(p_, xb)
        phi = cross_entropy(logits, yb)
        row = yb if hard else jnp.argmax(yb, axis=-1)
        psi = kd_regularizer(logits, gout[row])
        return phi + beta * psi

    l, g = jax.value_and_grad(loss_fn)(params)
    return jax.tree.map(lambda a, b: a - eta * b, params, g), l


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def output_to_model(model_apply, params, seeds_x, seeds_y, gout,
                    iters: int, batch: int, eta, beta, key):
    """K_s iterations of eq. (5). seeds_y can be int labels (FLD, Mix2FLD
    hard labels) or soft label vectors (MixFLD).  KD target row is chosen
    by the (arg-max for soft) ground-truth label.  ``key`` is required —
    there is deliberately no default (see module docstring).
    Returns (params, losses (iters,))."""
    n = seeds_x.shape[0]

    def step(carry, k):
        return _conversion_step(model_apply, seeds_x, seeds_y, gout, n,
                                batch, eta, beta, carry, k)

    with jax.named_scope("convert"):
        return jax.lax.scan(step, params, jax.random.split(key, iters))


def output_to_model_steps(model_apply, params, seeds_x, seeds_y, gout,
                          step_keys, iters, n_train, batch: int, eta, beta):
    """Masked-scan conversion for one config of a sweep grid.

    ``step_keys``: (K_max, 2) uint32 — the per-step PRNG keys, padded to
    the grid-wide maximum scan length (entries at index >= ``iters`` are
    never consumed); build them host-side as
    ``jax.random.split(base_key, iters)`` plus padding so live steps match
    :func:`output_to_model` exactly.  ``iters`` and ``n_train`` (the live
    prefix of a padded seed set — `randint` never samples pad rows) are
    traced per-config scalars; the caller vmaps this function over the
    grid axis.  Returns (params, losses (K_max,)) with masked steps
    contributing loss 0.
    """

    def step(carry, inp):
        k, i = inp
        new, l = _conversion_step(model_apply, seeds_x, seeds_y, gout,
                                  n_train, batch, eta, beta, carry, k)
        live = i < iters
        params = jax.tree.map(lambda a, b: jnp.where(live, a, b), new, carry)
        return params, jnp.where(live, l, 0.0)

    k_max = step_keys.shape[0]
    with jax.named_scope("convert"):
        return jax.lax.scan(step, params, (step_keys, jnp.arange(k_max)))
