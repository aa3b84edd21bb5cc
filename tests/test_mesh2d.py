"""2-D (grid x device) pod-mesh equivalence and shape resolution.

The sweep engine lays grid points along the ``"grid"`` axis and each
point's federated device axis along ``"data"`` (docs/pod_scale.md).
Grid points share no collectives — the psums stay over ``"data"`` — so
grid-axis sharding must be *bitwise* each point's vmapped program, while
device-axis sharding keeps the same reduction widths as the existing
1-D ``shard_devices`` path and must match it to 1e-6.

Comparisons are always reduction-width-matched: a (2, 4) mesh splits
device-axis sums into the same 4 partial sums as the 1-D 4-shard mesh,
so those two agree bitwise-or-epsilon on any host, whereas comparing
against the *unsharded* loop would measure float reassociation, not
correctness.  Shape-resolution tests are host-safe (pure arithmetic via
``avail=``); the sharded equivalence runs carry the ``multichip`` marker
and run on the CI job that forces 8 host devices.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.channel import ChannelConfig
from repro.core.program import ProgramOptions
from repro.core.protocols import FederatedConfig
from repro.data import partition_iid, synthetic_images
from repro.launch.mesh import grid_mesh_shape, make_grid_mesh
from repro.launch.sharding import federated_grid_pspecs
from repro.models.cnn import CNN
from repro.sweep import SweepRunner, engine_stats, make_grid, run_sweep

CH = ChannelConfig(num_devices=4, p_up_dbm=40.0)


@pytest.fixture(scope="module")
def data():
    x, y = synthetic_images(jax.random.PRNGKey(42), 1400)
    dev_x, dev_y = partition_iid(np.asarray(x[:1200]),
                                 np.asarray(y[:1200]), 4, 300, 10, seed=0)
    return dev_x, dev_y, jnp.asarray(x[1200:]), jnp.asarray(y[1200:])


def _base(**kw):
    cfg = dict(protocol="mix2fld", num_devices=4, local_iters=8,
               local_batch=16, server_iters=8, server_batch=16,
               max_rounds=3, n_seed=6, n_inverse=12, seed=0)
    cfg.update(kw)
    return FederatedConfig(**cfg)


def _assert_match(res_a, res_b, n, atol=1e-6):
    for g in range(n):
        ha, hb = res_a.history(g), res_b.history(g)
        np.testing.assert_allclose(ha["acc"], hb["acc"], atol=atol,
                                   err_msg=f"acc, point {g}")
        np.testing.assert_allclose(ha["loss"], hb["loss"], atol=atol,
                                   err_msg=f"loss, point {g}")
        assert ha["uplink_ok"] == hb["uplink_ok"], f"uplink_ok, point {g}"
        assert ha["converged_round"] == hb["converged_round"], \
            f"converged_round, point {g}"


# ---------------------------------------------------------------------------
# Shape resolution: pure arithmetic, host-safe
# ---------------------------------------------------------------------------

def test_grid_mesh_shape_explicit_validates():
    assert grid_mesh_shape(6, 4, shape=(2, 2), avail=8) == (2, 2)
    with pytest.raises(ValueError, match="grid size"):
        grid_mesh_shape(6, 4, shape=(4, 1), avail=8)
    with pytest.raises(ValueError, match="device population"):
        grid_mesh_shape(6, 4, shape=(1, 3), avail=8)
    with pytest.raises(ValueError, match="chips"):
        grid_mesh_shape(2, 4, shape=(2, 4), avail=4)
    with pytest.raises(ValueError, match=">= 1"):
        grid_mesh_shape(2, 4, shape=(0, 4), avail=8)


def test_grid_mesh_shape_auto_spends_grid_axis_first():
    # grid points are collective-free, so chips go to "grid" greedily
    assert grid_mesh_shape(2, 4, avail=8) == (2, 4)
    assert grid_mesh_shape(6, 4, avail=8) == (6, 1)
    assert grid_mesh_shape(8, 4, avail=8) == (8, 1)
    # primes that don't fit stay unsharded on that axis
    assert grid_mesh_shape(5, 4, avail=4) == (1, 4)
    # the 1-chip degeneration every host path relies on
    assert grid_mesh_shape(6, 4, avail=1) == (1, 1)


def test_make_grid_mesh_axes():
    mesh = make_grid_mesh(6, 4)
    assert mesh.axis_names == ("grid", "data")
    gs, ds = mesh.devices.shape
    assert 6 % gs == 0 and 4 % ds == 0
    assert gs * ds <= len(jax.devices())


def test_federated_grid_pspecs_contract():
    specs = federated_grid_pspecs()
    assert specs["gdev"] == jax.sharding.PartitionSpec("grid", "data")
    assert specs["gcfg"] == jax.sharding.PartitionSpec("grid")
    assert specs["data"] == jax.sharding.PartitionSpec("data")
    assert specs["replicated"] == jax.sharding.PartitionSpec()


def test_runner_clamps_oversized_mesh_request(data):
    """A mesh request beyond the host's chips degrades to what divides
    and fits (budget semantics), instead of erroring — and the resolved
    shape is reported on the program."""
    dev_x, dev_y, tx, ty = data
    grid = make_grid(_base(), CH, eta=(0.01, 0.02))
    runner = SweepRunner(CNN(), grid, dev_x, dev_y, tx, ty,
                         options=ProgramOptions(mesh_shape=(64, 64)))
    avail = len(jax.devices())
    for _, _, prog in runner._programs:
        gs, ds = prog.mesh_shape
        assert gs * ds <= avail
        assert 2 % gs == 0 and 4 % ds == 0


# ---------------------------------------------------------------------------
# Sharded equivalence on a real (forced 8-chip) multi-device host
# ---------------------------------------------------------------------------

@pytest.mark.multichip
def test_grid_axis_sharding_is_bitwise_vmapped(data):
    """Grid-axis-only sharding (2, 1): no collective anywhere touches a
    different operand set than the vmapped program, so each point's
    history must match that point swept alone bitwise, not just to
    tolerance.  Alone, not in a 2-point vmap: each shard runs a 1-point
    program, and the compiler may round a 2-wide vmap differently."""
    dev_x, dev_y, tx, ty = data
    grid_m = make_grid(_base(), CH, eta=(0.01, 0.02))
    runner = SweepRunner(CNN(), grid_m, dev_x, dev_y, tx, ty,
                         options=ProgramOptions(mesh_shape=(2, 1)))
    assert all(p.mesh_shape == (2, 1) for _, _, p in runner._programs)
    res_m = runner.run()
    for g, eta in enumerate((0.01, 0.02)):
        grid_v = make_grid(_base(), CH, eta=(eta,))
        res_v = run_sweep(CNN(), grid_v, dev_x, dev_y, tx, ty)
        hm, hv = res_m.history(g), res_v.history(0)
        np.testing.assert_array_equal(hm["acc"], hv["acc"])
        np.testing.assert_array_equal(hm["loss"], hv["loss"])
        assert hm["uplink_ok"] == hv["uplink_ok"]
        assert hm["converged_round"] == hv["converged_round"]


@pytest.mark.multichip
def test_grid_axis_sharding_gathers_nothing(data):
    """Each grid shard converts and evaluates its own points: the compiled
    (2, 1) program holds no all-gather (left to sharding propagation, the
    grid-vmapped conversion and evaluation were gathered to full grid
    width on every chip, which the TPU rounds differently)."""
    dev_x, dev_y, tx, ty = data
    grid = make_grid(_base(max_rounds=2), CH, eta=(0.01, 0.02))
    runner = SweepRunner(CNN(), grid, dev_x, dev_y, tx, ty,
                         options=ProgramOptions(mesh_shape=(2, 1)))
    (_, _, prog), = runner._programs
    hlo = prog._step_fn.lower(prog._state0, prog._xs).compile().as_text()
    assert "all-gather" not in hlo


@pytest.mark.multichip
def test_2d_mesh_matches_1d_device_sharding(data):
    """The full 2-D (2, 4) mesh against the pre-existing 1-D
    ``shard_devices`` path (4 device shards): identical psum widths on
    the device axis, so the grid axis must cost nothing numerically."""
    dev_x, dev_y, tx, ty = data
    grid_2d = make_grid(_base(), CH, eta=(0.01, 0.02))
    runner_2d = SweepRunner(CNN(), grid_2d, dev_x, dev_y, tx, ty,
                            options=ProgramOptions(mesh_shape=(2, 4)))
    assert all(p.mesh_shape == (2, 4) for _, _, p in runner_2d._programs)
    res_2d = runner_2d.run()
    grid_1d = make_grid(_base(shard_devices=True), CH, eta=(0.01, 0.02))
    runner_1d = SweepRunner(CNN(), grid_1d, dev_x, dev_y, tx, ty)
    assert runner_1d.mesh.devices.size == 4
    res_1d = runner_1d.run()
    _assert_match(res_2d, res_1d, 2)


@pytest.mark.multichip
def test_heterogeneous_sweep_on_2d_mesh_one_program_per_group(data):
    """A protocol-heterogeneous grid on the pod mesh still compiles
    exactly one program per structural group (the pod-scale acceptance
    property the pipeline benchmark gates)."""
    dev_x, dev_y, tx, ty = data
    engine_stats.reset()
    grid = make_grid(_base(local_iters=2, server_iters=2), CH,
                     protocol=("fl", "fd", "mix2fld"), eta=(0.01, 0.02))
    runner = SweepRunner(CNN(), grid, dev_x, dev_y, tx, ty,
                         options=ProgramOptions(mesh_shape=(2, 4)))
    runner.run()
    groups = len(grid.program_groups())
    assert engine_stats.programs == groups
    shapes = {p.mesh_shape for _, _, p in runner._programs}
    assert shapes == {(2, 4)}
