"""End-to-end protocol tests: FL / FD / FLD / MixFLD / Mix2FLD on the
paper's CNN with synthetic data (reduced iteration counts for CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.channel import ChannelConfig
from repro.core.protocols import PROTOCOLS, FederatedConfig, FederatedTrainer
from repro.data import partition_iid, partition_noniid, synthetic_images
from repro.models.cnn import CNN


@pytest.fixture(scope="module")
def data():
    key = jax.random.PRNGKey(0)
    x, y = synthetic_images(key, 4000)
    dev_x, dev_y = partition_iid(x[:3000], y[:3000], 5, 400, 10)
    return dev_x, dev_y, jnp.asarray(x[3000:]), jnp.asarray(y[3000:])


def _cfg(protocol, **kw):
    base = dict(protocol=protocol, num_devices=5, local_iters=60,
                local_batch=32, server_iters=60, server_batch=32,
                max_rounds=3, n_seed=10, n_inverse=20, seed=0)
    base.update(kw)
    return FederatedConfig(**base)


# symmetric channel so every protocol actually trains in 3 rounds
SYM = ChannelConfig(num_devices=5, p_up_dbm=40.0)


@pytest.mark.slow
@pytest.mark.parametrize("protocol", PROTOCOLS)
def test_protocol_runs_and_learns(protocol, data):
    dev_x, dev_y, tx, ty = data
    tr = FederatedTrainer(CNN(), _cfg(protocol), SYM)
    h = tr.run(dev_x, dev_y, tx, ty)
    assert len(h["acc"]) == 3
    assert all(np.isfinite(a) for a in h["acc"])
    assert h["acc"][-1] > 0.15  # better than chance after 3 rounds
    assert h["cum_time_s"][-1] > 0


@pytest.mark.slow
def test_mix2fld_seed_set_has_hard_labels_and_augments(data):
    dev_x, dev_y, tx, ty = data
    tr = FederatedTrainer(CNN(), _cfg("mix2fld", keep_seed_arrays=True), SYM)
    h = tr.run(dev_x, dev_y, tx, ty)
    meta = h["seeds"]
    assert meta["hard_labels"]  # hard labels after inverse-Mixup
    # N_I >= N_S: augmentation property (Sec. III-C)
    assert meta["n_train"] >= meta["n_uploaded"]
    seeds = h["seed_arrays"]  # opt-in full arrays agree with the summary
    assert seeds["train_y"].ndim == 1
    assert seeds["train_x"].shape[0] == meta["n_train"]
    assert seeds["uploaded"].shape[0] == meta["n_uploaded"]


@pytest.mark.slow
def test_mixfld_uploads_soft_labels(data):
    dev_x, dev_y, tx, ty = data
    tr = FederatedTrainer(CNN(), _cfg("mixfld", keep_seed_arrays=True), SYM)
    h = tr.run(dev_x, dev_y, tx, ty)
    assert not h["seeds"]["hard_labels"]
    seeds = h["seed_arrays"]
    assert seeds["train_y"].ndim == 2  # soft labels
    np.testing.assert_allclose(np.asarray(seeds["train_y"].sum(-1)), 1.0,
                               atol=1e-5)


def test_history_seeds_is_lightweight_metadata(golden_data):
    """By default histories carry JSON-ready seed metadata (counts, pair
    count, cycle-length histogram), not device arrays — serialized
    benchmark results stay small; arrays are opt-in."""
    import json
    dev_x, dev_y, tx, ty = golden_data
    tr = FederatedTrainer(CNN(), _golden_cfg("mix2fld", max_rounds=1),
                          GOLDEN_CH)
    h = tr.run(dev_x, dev_y, tx, ty)
    assert "seed_arrays" not in h
    meta = h["seeds"]
    assert json.loads(json.dumps(meta))["n_train"] == meta["n_train"]
    assert meta["n_uploaded"] == 4 * 6  # D * n_seed
    assert meta["n_pairs"] >= 1
    assert meta["hard_labels"]
    # pair entries count as length-2 cycles in the histogram; keys are
    # strings so the dict is identical after a JSON round-trip
    assert meta["cycle_hist"].get("2") == meta["n_pairs"]
    assert sum(int(k) * v for k, v in meta["cycle_hist"].items()) >= \
        2 * meta["n_pairs"]


def test_mix2up_privacy_exceeds_mixup_privacy(data):
    """Table III vs Table II: inversely mixed-up samples are farther from
    their raw constituents than plain mixed-up uploads."""
    from repro.core.privacy import mean_privacy
    dev_x, dev_y, tx, ty = data
    fc = _cfg("mix2fld", lam=0.4)
    tr = FederatedTrainer(CNN(), fc, SYM)
    seeds = tr.collect_seeds(jnp.asarray(dev_x), jnp.asarray(dev_y),
                             jax.random.PRNGKey(7))
    p_mixup = mean_privacy(seeds["uploaded"], seeds["raw_pairs"])
    # Mix2up samples vs the raws of *their* constituents is what Table III
    # reports; conservatively compare against all uploaded raws pairwise
    n = min(seeds["train_x"].shape[0], seeds["raw_pairs"].shape[0])
    p_mix2 = mean_privacy(seeds["train_x"][:n], seeds["raw_pairs"][:n])
    assert p_mix2 > p_mixup - 0.5  # never catastrophically worse


def test_noniid_partition_matches_paper_recipe():
    key = jax.random.PRNGKey(1)
    x, y = synthetic_images(key, 8000)
    dev_x, dev_y = partition_noniid(x, y, 10)
    assert dev_x.shape[0] == 10
    for d in range(10):
        counts = np.bincount(dev_y[d], minlength=10)
        assert sorted(counts)[:2] == [2, 2]          # two rare labels
        assert all(c == 62 for c in sorted(counts)[2:])  # rest 62 each
        assert counts.sum() == 500


@pytest.mark.slow
def test_fd_uses_kd_after_first_round(data):
    """FD devices keep their own weights; accuracy should keep rising."""
    dev_x, dev_y, tx, ty = data
    tr = FederatedTrainer(CNN(), _cfg("fd", max_rounds=4), SYM)
    h = tr.run(dev_x, dev_y, tx, ty)
    assert h["acc"][-1] > h["acc"][0]


def test_collect_seeds_batched_invariants(data):
    """The device-axis-batched pipeline keeps the old path's guarantees:
    uploaded set is (D*Ns, ...), inverse set has hard labels in range,
    pairing produced cross-device symmetric pairs, and the inverse set
    meets the N_I augmentation target."""
    dev_x, dev_y, _, _ = data
    fc = _cfg("mix2fld")
    tr = FederatedTrainer(CNN(), fc, SYM)
    seeds = tr.collect_seeds(jnp.asarray(dev_x), jnp.asarray(dev_y),
                             jax.random.PRNGKey(3))
    D, Ns = fc.num_devices, fc.n_seed
    assert seeds["uploaded"].shape[0] == D * Ns
    assert seeds["raw_pairs"].shape[:2] == (D * Ns, 2)
    assert seeds["train_x"].shape[0] == fc.n_inverse * D
    assert seeds["train_x"].shape[1:] == seeds["uploaded"].shape[1:]
    assert seeds["train_y"].ndim == 1
    y = np.asarray(seeds["train_y"])
    assert y.min() >= 0 and y.max() < fc.num_classes
    assert seeds["n_pairs"] > 0


def test_collect_seeds_lam_half_degrades_to_soft_labels(data):
    """lam = 0.5 makes Prop. 1 singular; the pipeline must fall back to
    soft-label (MixFLD-style) training instead of dividing by zero."""
    dev_x, dev_y, _, _ = data
    tr = FederatedTrainer(CNN(), _cfg("mix2fld", lam=0.5), SYM)
    seeds = tr.collect_seeds(jnp.asarray(dev_x), jnp.asarray(dev_y),
                             jax.random.PRNGKey(5))
    assert seeds["train_y"].ndim == 2  # soft labels
    assert bool(jnp.isfinite(seeds["train_x"]).all())


def test_collect_seeds_fld_draws_without_replacement(data):
    dev_x, dev_y, _, _ = data
    fc = _cfg("fld")
    tr = FederatedTrainer(CNN(), fc, SYM)
    seeds = tr.collect_seeds(jnp.asarray(dev_x), jnp.asarray(dev_y),
                             jax.random.PRNGKey(4))
    assert seeds["train_x"].shape[0] == fc.num_devices * fc.n_seed
    assert seeds["train_y"].shape == (fc.num_devices * fc.n_seed,)


def test_collect_seeds_fld_rejects_seed_budget_above_local_data():
    """n_seed > n_local used to surface as an opaque JAX error from
    ``random.choice(..., replace=False)``; it must be a clear ValueError
    at the seed-prep boundary."""
    from repro.core.protocols import collect_seeds
    key = jax.random.PRNGKey(0)
    dev_x = jax.random.normal(key, (3, 8, 28, 28, 1))  # n_local = 8
    dev_y = jax.random.randint(key, (3, 8), 0, 10)
    fc = FederatedConfig(protocol="fld", num_devices=3, n_seed=9)
    with pytest.raises(ValueError, match="without replacement"):
        collect_seeds(fc, dev_x, dev_y, key)
    # the mixup paths' equivalent bound: pairs need >= 2 local samples
    tiny_x, tiny_y = dev_x[:, :1], dev_y[:, :1]
    fc2 = FederatedConfig(protocol="mix2fld", num_devices=3, n_seed=1,
                          n_inverse=1)
    with pytest.raises(ValueError, match="at least 2 local samples"):
        collect_seeds(fc2, tiny_x, tiny_y, key)


def test_federated_config_validates_fields():
    with pytest.raises(ValueError, match="unknown protocol"):
        FederatedConfig(protocol="nonsense")
    with pytest.raises(ValueError, match="n_seed"):
        FederatedConfig(n_seed=0)
    with pytest.raises(ValueError, match="n_inverse"):
        FederatedConfig(n_inverse=0)
    with pytest.raises(ValueError, match="lam"):
        FederatedConfig(lam=1.5)


# ---------------------------------------------------------------------------
# Fixed-seed regression goldens + sharded-vs-vmapped equivalence (fast
# configs: these run in the tier-1 suite and lock the round loop down)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def golden_data():
    x, y = synthetic_images(jax.random.PRNGKey(42), 1400)
    dev_x, dev_y = partition_iid(np.asarray(x[:1200]), np.asarray(y[:1200]),
                                 4, 300, 10, seed=0)
    return dev_x, dev_y, jnp.asarray(x[1200:]), jnp.asarray(y[1200:])


def _golden_cfg(protocol, **kw):
    base = dict(protocol=protocol, num_devices=4, local_iters=8,
                local_batch=16, server_iters=8, server_batch=16,
                max_rounds=3, n_seed=6, n_inverse=12, seed=0)
    base.update(kw)
    return FederatedConfig(**base)


GOLDEN_CH = ChannelConfig(num_devices=4, p_up_dbm=40.0)

# 3-round histories recorded when the sharded round loop / Pallas hot
# path landed; if an *intentional* numerics change lands, regenerate with
# the snippet in docs/sharded_round_loop.md §Regression goldens.
# mix2fld re-recorded when the segment/sort cycle search replaced the
# budgeted DFS (higher cycle yield changes the round-1 inverse set).
# All five re-recorded on JAX 0.9.0, whose default threefry PRNG
# partitioning (jax_threefry_partitionable=True) draws different streams
# than the 0.4.37 default the earlier goldens were recorded under.
GOLDEN = {
    "fl": dict(
        acc=[0.225, 0.225, 0.19],
        loss=[2.313039, 2.278673, 2.267214],
        latency_s=[0.061, 0.063, 0.061]),
    "fd": dict(
        acc=[0.13, 0.18, 0.1],
        loss=[2.313039, 2.311103, 2.290004],
        latency_s=[0.002, 0.002, 0.002]),
    "fld": dict(
        acc=[0.065, 0.065, 0.065],
        loss=[2.313039, 2.344419, 2.341425],
        latency_s=[0.026, 0.022, 0.022]),
    "mixfld": dict(
        acc=[0.09, 0.16, 0.175],
        loss=[2.313039, 2.309267, 2.319884],
        latency_s=[0.026, 0.022, 0.022]),
    "mix2fld": dict(
        acc=[0.09, 0.115, 0.09],
        loss=[2.313039, 2.353428, 2.359449],
        latency_s=[0.026, 0.022, 0.022]),
}


@pytest.mark.parametrize("protocol", sorted(GOLDEN))
def test_protocol_golden_history(protocol, golden_data):
    """Fixed-seed 3-round histories must reproduce the recorded goldens:
    catches silent numerics drift anywhere on the round loop (local SGD,
    kernels, aggregation, channel, conversion)."""
    dev_x, dev_y, tx, ty = golden_data
    tr = FederatedTrainer(CNN(), _golden_cfg(protocol), GOLDEN_CH)
    h = tr.run(dev_x, dev_y, tx, ty)
    want = GOLDEN[protocol]
    np.testing.assert_allclose(h["acc"], want["acc"], atol=1e-4)
    np.testing.assert_allclose(h["loss"], want["loss"], atol=1e-4)
    np.testing.assert_allclose(h["round_latency_s"], want["latency_s"],
                               rtol=1e-6)


@pytest.mark.parametrize("protocol", ["fd", "mix2fld"])
def test_sharded_round_loop_matches_vmapped(protocol, golden_data):
    """shard_devices=True on a 1-chip mesh must reproduce the vmapped
    path's fixed-seed history within 1e-4 (the psum collectives reduce to
    the tensordot/einsum reductions when there is one shard)."""
    dev_x, dev_y, tx, ty = golden_data
    tr_v = FederatedTrainer(CNN(), _golden_cfg(protocol), GOLDEN_CH)
    h_v = tr_v.run(dev_x, dev_y, tx, ty)
    tr_s = FederatedTrainer(CNN(), _golden_cfg(protocol, shard_devices=True),
                            GOLDEN_CH)
    assert tr_s.mesh is not None and tr_v.mesh is None
    h_s = tr_s.run(dev_x, dev_y, tx, ty)
    np.testing.assert_allclose(h_s["acc"], h_v["acc"], atol=1e-4)
    np.testing.assert_allclose(h_s["loss"], h_v["loss"], atol=1e-4)
    assert h_s["round_latency_s"] == h_v["round_latency_s"]
    assert h_s["converged_round"] == h_v["converged_round"]
    np.testing.assert_allclose(np.asarray(tr_s.last_dev_gout),
                               np.asarray(tr_v.last_dev_gout), atol=1e-5)


@pytest.mark.multichip
def test_sharded_round_loop_multichip_really_shards(golden_data):
    """Pod validation (auto-skipped on 1-chip hosts): with >1 chip the
    device mesh must actually split the population, and the psum
    round loop must still match the vmapped oracle."""
    dev_x, dev_y, tx, ty = golden_data
    tr_s = FederatedTrainer(CNN(), _golden_cfg("mix2fld",
                                               shard_devices=True),
                            GOLDEN_CH)
    assert tr_s.mesh.devices.size > 1
    h_s = tr_s.run(dev_x, dev_y, tx, ty)
    tr_v = FederatedTrainer(CNN(), _golden_cfg("mix2fld"), GOLDEN_CH)
    h_v = tr_v.run(dev_x, dev_y, tx, ty)
    np.testing.assert_allclose(h_s["acc"], h_v["acc"], atol=1e-4)
    np.testing.assert_allclose(h_s["loss"], h_v["loss"], atol=1e-4)
    np.testing.assert_allclose(np.asarray(tr_s.last_dev_gout),
                               np.asarray(tr_v.last_dev_gout), atol=1e-5)


def test_sharded_mesh_auto_shard_count():
    """make_device_mesh picks the largest divisor of |D| that fits the
    local chip count, and rejects non-divisible explicit counts."""
    from repro.launch.mesh import make_device_mesh
    mesh = make_device_mesh(10)
    assert mesh.axis_names == ("data",)
    assert 10 % mesh.devices.size == 0
    with pytest.raises(ValueError):
        make_device_mesh(10, shards=3)


# SNR target no link can meet: every uplink AND downlink outages, so the
# global state never changes after round 1 — the spurious-convergence trap
ALL_OUT = ChannelConfig(num_devices=4, theta=1e9)


@pytest.mark.parametrize("protocol", ["fl", "fd", "mix2fld"])
def test_total_outage_rounds_never_record_convergence(protocol,
                                                      golden_data):
    """Regression: with every uplink failing, g_params/gout stay frozen,
    rel == 0 < eps, and the old check recorded converged_round = 2 on a
    round where *nothing arrived*.  The check must be gated on at least
    one decoded uplink."""
    dev_x, dev_y, tx, ty = golden_data
    fc = _golden_cfg(protocol, eps=10.0)  # any rel passes the threshold
    tr = FederatedTrainer(CNN(), fc, ALL_OUT)
    h = tr.run(dev_x, dev_y, tx, ty)
    assert h["uplink_ok"] == [0, 0, 0]
    assert h["converged_round"] is None


def test_convergence_still_fires_when_uplinks_decode(golden_data):
    """Control for the outage gate: same eps on a clean channel records
    the first checkable round as before."""
    dev_x, dev_y, tx, ty = golden_data
    tr = FederatedTrainer(CNN(), _golden_cfg("fd", eps=10.0), GOLDEN_CH)
    h = tr.run(dev_x, dev_y, tx, ty)
    assert all(n > 0 for n in h["uplink_ok"])
    assert h["converged_round"] == 2


def test_round_once_resume_matches_uninterrupted_run(golden_data):
    """The factored step is genuinely resumable: running rounds 1..3
    through a fresh state object round-by-round — with a full state
    hand-off between rounds, as the serving driver does across process
    restarts — reproduces run()'s history bit-for-bit."""
    dev_x, dev_y, tx, ty = golden_data
    tr = FederatedTrainer(CNN(), _golden_cfg("mix2fld"), GOLDEN_CH)
    h = tr.run(dev_x, dev_y, tx, ty)
    tr2 = FederatedTrainer(CNN(), _golden_cfg("mix2fld"), GOLDEN_CH)
    state = tr2.init_state()
    recs = []
    for _ in range(3):
        # rebuild the dict each round: nothing may depend on object
        # identity carrying over (a restore produces fresh arrays)
        state = dict(state)
        state, rec = tr2.round_once(state, dev_x, dev_y, tx, ty)
        recs.append(rec)
    assert [r["acc"] for r in recs] == h["acc"]
    assert [r["loss"] for r in recs] == h["loss"]
    assert [r["round_latency_s"] for r in recs] == h["round_latency_s"]
    assert [r["uplink_ok"] for r in recs] == h["uplink_ok"]
    assert state["converged_round"] == h["converged_round"]


# downlink that never decodes (p_dn far below the SNR target) vs always
NO_DN = ChannelConfig(num_devices=5, p_up_dbm=40.0, p_dn_dbm=-60.0)


def test_fd_downlink_gating_keeps_previous_gout(data):
    """A device whose downlink failed must keep its previous G_out rather
    than receiving the new one for free."""
    dev_x, dev_y, tx, ty = data
    fc = _cfg("fd", max_rounds=2, local_iters=10)
    tr = FederatedTrainer(CNN(), fc, NO_DN)
    tr.run(dev_x, dev_y, tx, ty)
    C = fc.num_classes
    # every downlink outages => all devices still hold the uniform prior
    np.testing.assert_allclose(np.asarray(tr.last_dev_gout),
                               np.full((5, C, C), 1.0 / C), atol=1e-6)
    # control: with a clean downlink the tables are refreshed
    tr2 = FederatedTrainer(CNN(), fc, SYM)
    tr2.run(dev_x, dev_y, tx, ty)
    assert float(np.abs(np.asarray(tr2.last_dev_gout) - 1.0 / C).max()) > 1e-3
