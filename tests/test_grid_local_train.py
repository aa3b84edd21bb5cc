"""The grid's local SGD: ``make_grid_local_train`` trains G x D models on
one flat vmap axis and must equal, point by point, the single-point
local train vmapped over that point's D devices — bitwise, for shared,
per-config and sampled data layouts.  The compiled sweep program must
carry local SGD's pooling on one model axis (rank 5), not two."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.channel import ChannelConfig
from repro.core.protocols import (FederatedConfig, make_grid_local_train,
                                  make_local_train)
from repro.data import partition_iid, synthetic_images
from repro.models.cnn import CNN
from repro.sweep import SweepRunner, make_grid

C, ITERS, BATCH, N = 10, 3, 4, 20


def _inputs(G, D, layout):
    """(params, x, y, keys, gout, eta, beta, n_loc, per-point data) for a
    G x D grid; the per-point data is what point g's devices train on."""
    model = CNN()
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    inits = jax.vmap(model.init)(jax.random.split(ks[0], G * D))
    params = jax.tree.map(lambda a: a.reshape((G, D) + a.shape[1:]), inits)
    keys = jax.random.split(ks[1], G * D).reshape(G, D, -1)
    gout = jax.nn.softmax(jax.random.normal(ks[2], (G, D, C, C)), axis=-1)
    eta = jnp.linspace(0.01, 0.05, G)
    beta = jnp.linspace(0.5, 1.5, G)
    n_loc = jnp.asarray([N - 3 * (g % 2) for g in range(G)], jnp.int32)

    def images(k, lead):
        x, y = synthetic_images(k, int(np.prod(lead)) * N)
        return (jnp.asarray(x).reshape(lead + (N,) + x.shape[1:]),
                jnp.asarray(y).reshape(lead + (N,)))

    if layout == "shared":
        x, y = images(ks[3], (D,))
        per_point = [(x, y)] * G
    elif layout == "per_config":
        x, y = images(ks[3], (G, D))
        per_point = [(x[g], y[g]) for g in range(G)]
    else:  # sampled: each point's cohort gathered off a shared pool
        px, py = images(ks[3], (D + 2,))
        chrt = jnp.stack([jnp.sort(jax.random.permutation(k, D + 2)[:D])
                          for k in jax.random.split(ks[4], G)])
        x, y = px[chrt], py[chrt]
        per_point = [(px[c], py[c]) for c in chrt]
    return params, x, y, keys, gout, eta, beta, n_loc, per_point


@pytest.mark.parametrize("layout,G", [("shared", 3), ("per_config", 3),
                                      ("sampled", 3), ("shared", 1)],
                         ids=["shared", "per_config", "sampled", "G1"])
def test_grid_local_train_equals_per_point_vmap(layout, G):
    D = 4
    params, x, y, keys, gout, eta, beta, n_loc, per_point = \
        _inputs(G, D, layout)
    use_kd = jnp.asarray(True)
    grid_lt = jax.jit(make_grid_local_train(
        CNN().apply, C, ITERS, BATCH, per_config_data=layout != "shared"))
    got = grid_lt(params, x, y, keys, gout, use_kd, eta, beta, n_loc)

    point_lt = jax.jit(jax.vmap(
        make_local_train(CNN().apply, C, ITERS, BATCH),
        in_axes=(0, 0, 0, 0, 0, None, None, None, None)))
    for g, (xg, yg) in enumerate(per_point):
        want = point_lt(jax.tree.map(lambda a: a[g], params), xg, yg,
                        keys[g], gout[g], use_kd, eta[g], beta[g],
                        n_loc[g])
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert a.shape[:2] == (G, D)
            np.testing.assert_array_equal(a[g], b, err_msg=f"point {g}")


_POOL_OPS = ("reduce-window", "select-and-scatter", "convolution")


def test_sweep_program_pools_on_one_model_axis():
    """Lowered sweep scan: every pooling, pooling-gradient and
    convolution op is rank 5 or less, and local SGD's pooling runs on
    the flat G·D model axis (a vmap of a vmap would make it rank 6)."""
    G, D = 2, 4
    x, y = synthetic_images(jax.random.PRNGKey(42), 1300)
    dev_x, dev_y = partition_iid(np.asarray(x[:1200]), np.asarray(y[:1200]),
                                 D, 300, C, seed=0)
    fc = FederatedConfig(protocol="mix2fld", num_devices=D, local_iters=2,
                         local_batch=16, server_iters=2, server_batch=16,
                         max_rounds=1, n_seed=6, n_inverse=12, seed=0)
    grid = make_grid(fc, ChannelConfig(num_devices=D, p_up_dbm=40.0),
                     eta=(0.01, 0.02))
    runner = SweepRunner(CNN(), grid, dev_x, dev_y, jnp.asarray(x[1200:]),
                         jnp.asarray(y[1200:]))
    prog = runner._programs[0][2]
    hlo = prog._step_fn.lower(prog._state0, prog._xs).as_text(dialect="hlo")

    shapes = {}
    for line in hlo.splitlines():
        for op in _POOL_OPS:
            head, sep, _ = line.partition(f" {op}(")
            if sep and "=" in head:
                result = head.split("=", 1)[1]
                shapes.setdefault(op, []).extend(
                    tuple(int(d) for d in s.split(",") if d)
                    for s in re.findall(r"[a-z]\w*\[([\d,]*)\]", result))
    assert shapes.get("reduce-window") and shapes.get("convolution")
    for op, dims in shapes.items():
        assert max(len(d) for d in dims) <= 5, (op, dims)
    assert any(d[0] == G * D for d in shapes["reduce-window"]
               if len(d) == 5), shapes["reduce-window"]
