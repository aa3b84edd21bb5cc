"""Whole runs of one chip-benchmark cell, cut to a CPU size: the driver
runs and its check passes; planted faults make the check fail.  Each ``test_chipbench_<cell>.py`` file supplies the
``cell`` fixture, so the cells run in parallel test workers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny as tiny


def test_cell_runs_and_is_correct(cell):
    result = tiny.run(cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["programs_lowered_in_window"] == 0
    assert set(result["metrics"]) == {"rounds_per_s", "setup_s"}
    assert list(result)[-1] == "checks"


def _frozen_round(monkeypatch):
    """Every round returns the state it was given."""
    from repro.core import protocols

    real = protocols.FederatedTrainer.round_once

    def round_once(self, state, *a, **kw):
        _, rec = real(self, state, *a, **kw)
        return protocols.RoundState.from_mapping(state), rec

    monkeypatch.setattr(protocols.FederatedTrainer, "round_once", round_once)
    real_step = protocols.make_grid_round_step

    def grid_step(*a, **kw):
        step = real_step(*a, **kw)
        return lambda state, xs: (state, step(state, xs)[1])

    from repro.sweep import engine
    monkeypatch.setattr(engine, "make_grid_round_step", grid_step)


def _half_batch(monkeypatch):
    """Local SGD takes the loss over the first half of every batch."""
    from repro.core import protocols
    from repro.core.losses import fd_loss

    def make_local_train(apply_fn, num_classes, local_iters, local_batch):
        def local_train(params, x, y, key, gout, use_kd, eta, beta, n_loc):
            def step(carry, k):
                p, out_sum, cnt = carry
                idx = jax.random.randint(k, (local_batch,), 0, n_loc)
                xb, yb = x[idx][:local_batch // 2], y[idx][:local_batch // 2]

                def loss_fn(p_):
                    logits = apply_fn(p_, xb)
                    return fd_loss(logits, yb, gout,
                                   jnp.where(use_kd, beta, 0.0))[0], logits

                (l, logits), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
                p = jax.tree.map(lambda a, b_: a - eta * b_, p, g)
                probs = jax.nn.softmax(logits, axis=-1)
                oh = jax.nn.one_hot(yb, num_classes)
                return (p, out_sum + oh.T @ probs, cnt + oh.sum(0)), l

            init = (params, jnp.zeros((num_classes, num_classes)),
                    jnp.zeros((num_classes,)))
            (params, s, c), losses = jax.lax.scan(
                step, init, jax.random.split(key, local_iters))
            return params, s / jnp.maximum(c[:, None], 1.0), c, losses.mean()
        return local_train

    monkeypatch.setattr(protocols, "make_local_train", make_local_train)


@pytest.mark.parametrize("fault", [_frozen_round, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_planted_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    result = tiny.run(cell)
    assert not result["correct"], result["checks"]


def swapped_seed_labels(monkeypatch):
    """Round-1 seed prep hands the server its samples with every label
    moved one place on."""
    from repro.core import protocols, seed_prep

    real = seed_prep.collect_seeds

    def collect_seeds(*a, **kw):
        out = real(*a, **kw)
        if out is not None and np.asarray(out["train_y"]).ndim == 1:
            out = {**out, "train_y": jnp.roll(out["train_y"], 1)}
        return out

    monkeypatch.setattr(seed_prep, "collect_seeds", collect_seeds)
    monkeypatch.setattr(protocols, "collect_seeds", collect_seeds)
