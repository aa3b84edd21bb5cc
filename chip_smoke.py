#!/usr/bin/env python3
"""Smoke test of the Mix2FLD round on a TPU, through the normal entry points.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded device axis

One chip runs the paper's own model (the ``cnn`` registry model on the
``digits`` task, 12,490 weights) at full width, with random weights made
from fixed seeds:

* ``device``  — the first JAX device is a TPU (no CPU fallback);
* ``kernels`` — Pallas kernels compile instead of interpreting, and agree
  with the pure-jnp references at the round's shapes within 1e-5;
* ``trainer`` — ``FederatedTrainer``: Mix2FLD, 10 devices, the
  ``FederatedConfig`` defaults, the paper's 23/40 dBm channel, 3 rounds;
  the local-train program holds ``tpu_custom_call``;
* ``service`` — ``FederatedService`` with churn, stragglers and a
  checkpoint every round (the ``launch.service`` smoke setup), one served
  batch, and a restore from round 2 whose tail is identical;
* ``sweep``   — ``SweepRunner`` over fl/fd/mix2fld x two uplink powers,
  one compiled program per protocol.

``--chips 4`` runs only the mesh path and its references: a
``shard_devices`` trainer round on a 4-shard ``"data"`` mesh, and sweeps
on (2, 1) and (2, 2) ``("grid", "data")`` meshes, each within 1e-6 of a
reference that runs the same program width per chip (see
:func:`phase_mesh`).

Timings are host-clock seconds, printed for information only.  Any
failed check exits non-zero; the last line of a passing run is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.channel import ChannelConfig  # noqa: E402
from repro.core.losses import fd_loss  # noqa: E402
from repro.core.program import ProgramOptions  # noqa: E402
from repro.core.protocols import FederatedConfig, FederatedTrainer  # noqa: E402
from repro.data import partition_iid, synthetic_images  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.distill_loss import distill_phi_psi  # noqa: E402
from repro.kernels.mixup_kernel import mixup_pallas  # noqa: E402
from repro.kernels.runtime import default_interpret  # noqa: E402
from repro.launch import service  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.sweep import SweepRunner, engine_stats, make_grid  # noqa: E402

CKPT_DIR = ROOT / ".chip_smoke" / "ckpt"   # git-ignored
KERNEL_TOL = 1e-5
MESH_TOL = 1e-6


def require(cond, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def report(phase: str, **fields) -> None:
    dev = jax.devices()[0]
    kv = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] device={dev.device_kind!r} {kv}", flush=True)


def dataset(num_devices: int, per_device: int = 500, n_test: int = 1000):
    """IID digits shards: (D, per_device, 28, 28, 1) plus a test set."""
    n = num_devices * per_device
    x, y = synthetic_images(jax.random.PRNGKey(0), n + n_test)
    dev_x, dev_y = partition_iid(x[:n], y[:n], num_devices, per_device, 10,
                                 seed=0)
    return dev_x, dev_y, jnp.asarray(x[n:]), jnp.asarray(y[n:])


class ProgramCounter:
    """Counts the programs JAX lowers: one per new jitted shape or static
    value, so a round that lowers none runs only compiled code."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1


class RoundClock:
    """Host-clock stamps plus lowered-program counts, one per round."""

    def __init__(self, programs: ProgramCounter):
        self.programs = programs
        self.stamps = [time.perf_counter()]
        self.counts = [programs.n]

    def tick(self, *_):
        self.stamps.append(time.perf_counter())
        self.counts.append(self.programs.n)

    def fields(self) -> dict:
        """Per-round seconds and new programs; warm rounds/s over the
        trailing rounds that lowered nothing new."""
        secs = np.diff(self.stamps)
        new = np.diff(self.counts)
        warm = []
        for s_, n_ in zip(secs[::-1], new[::-1]):
            if n_:
                break
            warm.append(s_)
        return {"round_s": secs.tolist(), "new_programs": new.tolist(),
                "warm_rounds_per_s": (len(warm) / sum(warm) if warm
                                      else "not measured")}


def max_dev(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64) -
                               np.asarray(b, np.float64))))


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------

def phase_kernels(programs):
    require(default_interpret() is False,
            "kernels.runtime.default_interpret() is True on the chip")
    key = jax.random.PRNGKey(1)
    worst = 0.0
    # local SGD and the server conversion draw 16-sample batches over 10
    # classes; the trainer vmaps local SGD over 10 devices
    for lead in ((), (10,)):
        k1, k2, k3, key = jax.random.split(key, 4)
        z = jax.random.normal(k1, lead + (16, 10)) * 3.0
        y = jax.random.randint(k2, lead + (16,), 0, 10)
        gout = jax.nn.softmax(jax.random.normal(k3, (10, 10)))

        def device_step(use_kernel):
            """One device's batch loss and its logits gradient, as local
            SGD takes them (vmapped over devices when ``lead``)."""
            def one(zz, yy):
                return fd_loss(zz, yy, gout, 0.01, use_kernel=use_kernel)[0]
            step = jax.value_and_grad(one)
            for _ in lead:
                step = jax.vmap(step)
            return jax.jit(step)

        v_k, g_k = device_step(True)(z, y)
        v_r, g_r = device_step(False)(z, y)
        worst = max(worst, max_dev(v_k, v_r), max_dev(g_k, g_r))
        if not lead:  # per-sample (phi, psi) against the fused-loss oracle
            phi, psi = jax.jit(distill_phi_psi)(z, y, gout[y])
            want = ref.distill_loss_ref(z, y, gout[y], 0.5)
            worst = max(worst, max_dev(phi + 0.5 * psi, want))
    for n in (100, 50):  # device-side Mixup (D x N_S) and inverse pairs
        k1, k2, k3, key = jax.random.split(key, 4)
        a = jax.random.normal(k1, (n, 784))
        b = jax.random.normal(k2, (n, 784))
        lam = jax.random.uniform(k3, (n,), minval=-0.2, maxval=1.2)
        got = mixup_pallas(a, b, lam, 1.0 - lam)
        worst = max(worst, max_dev(got, ref.mixup_ref(a, b, lam, 1.0 - lam)))
    require(worst <= KERNEL_TOL,
            f"kernel parity {worst:.3g} exceeds {KERNEL_TOL}")
    report("kernels", interpret=default_interpret(), max_abs_dev=worst)


def phase_trainer(programs):
    t0 = time.perf_counter()
    fc = FederatedConfig(protocol="mix2fld", max_rounds=3)
    ch = ChannelConfig(num_devices=fc.num_devices)  # 23 dBm up, 40 dBm down
    data = dataset(fc.num_devices)
    tr = FederatedTrainer(None, fc, ch)
    state = tr.init_state()
    n_params = sum(p.size for p in jax.tree.leaves(state.g_params))
    require(n_params == 12490, f"paper CNN has {n_params} weights, not 12490")
    keys = jax.random.split(jax.random.PRNGKey(0), fc.num_devices)
    hlo = tr._local_train.lower(state.dev_params, data[0], data[1], keys,
                                state.dev_gout, jnp.asarray(True)).as_text()
    require("tpu_custom_call" in hlo,
            "local-train HLO holds no tpu_custom_call (kernel interpreted?)")
    setup = time.perf_counter() - t0
    cold = RoundClock(programs)
    hist = tr.run(*data, log=cold.tick)
    # the rerun replays the same seeded job on compiled programs only
    warm = RoundClock(programs)
    rerun = tr.run(*data, log=warm.tick)
    require(rerun["acc"] == hist["acc"] and rerun["loss"] == hist["loss"],
            "a rerun of the same seeded job gave another history")
    require(len(hist["acc"]) == 3, "trainer did not run 3 rounds")
    require(np.all(np.isfinite(hist["acc"])) and
            np.all(np.isfinite(hist["loss"])), f"non-finite history {hist}")
    n_up = hist["seeds"]["n_uploaded"]
    require(n_up == fc.num_devices * fc.n_seed,
            f"{n_up} seeds uploaded, want {fc.num_devices * fc.n_seed}")
    report("trainer", setup_s=setup, **cold.fields(), acc=hist["acc"],
           loss=hist["loss"], seeds_uploaded=n_up)
    report("trainer_rerun", **warm.fields())


def phase_service(programs):
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    args = service._parser().parse_args(
        ["--rounds", "4", "--ckpt-dir", str(CKPT_DIR)])
    t0 = time.perf_counter()
    svc, _ = service._smoke_setup(args)
    setup = time.perf_counter() - t0
    clock = RoundClock(programs)
    recs = svc.run_rounds(args.rounds, log=clock.tick)
    require(all(np.isfinite(r["acc"]) and np.isfinite(r["loss"])
                for r in recs), "non-finite service record")
    saved = sorted(p.name for p in CKPT_DIR.iterdir())
    require(len(saved) >= args.rounds, f"checkpoints written: {saved}")
    batch = np.asarray(svc._data[0])[0][: svc.endpoint.batch_size]
    preds = svc.serve(batch)
    require(preds.shape == (svc.endpoint.batch_size,) and
            np.all((preds >= 0) & (preds < svc.fc.num_classes)),
            f"served predictions {preds}")
    mid = 2
    svc2, _ = service._smoke_setup(args)
    require(svc2.restore(step=mid) == mid, "restore did not land on step 2")
    tail = svc2.run_rounds(args.rounds - mid)
    want, have = service._tail(recs[mid:]), service._tail(tail)
    require(want == have, f"resumed tail differs:\n {want}\n {have}")
    report("service", setup_s=setup, **clock.fields(),
           cohorts=[r["n_active"] for r in recs],
           stragglers=sum(r["n_straggle"] for r in recs),
           served=int(preds.shape[0]), resume="identical")


def phase_sweep(programs):
    fc = FederatedConfig(protocol="mix2fld", max_rounds=3)
    ch = ChannelConfig(num_devices=fc.num_devices)
    data = dataset(fc.num_devices)
    grid = make_grid(fc, ch, protocol=("fl", "fd", "mix2fld"),
                     p_up_dbm=(23.0, 40.0))
    engine_stats.reset()
    t0 = time.perf_counter()
    runner = SweepRunner(None, grid, *data)
    groups = len(grid.program_groups())
    require(runner.programs == groups == 3,
            f"{runner.programs} programs for {groups} protocol groups")
    cold = runner.run()
    cold_s = time.perf_counter() - t0
    warm = runner.run()
    require(engine_stats.traces == groups,
            f"{engine_stats.traces} traces for {groups} groups")
    for res in (cold, warm):
        require(np.all(np.isfinite(res.acc)) and
                np.all(np.isfinite(res.loss)), "non-finite sweep results")
    require(np.array_equal(cold.loss, warm.loss), "warm rerun differs")
    report("sweep", points=grid.size, programs=runner.programs,
           setup_and_first_run_s=cold_s,
           warm_rounds_per_s=grid.size * cold.rounds / warm.wall_s,
           final_acc=warm.acc[:, -1].tolist())


# ---------------------------------------------------------------------------
# Four-chip phase: the sharded device axis against its vmapped reference
# ---------------------------------------------------------------------------

def shard_by_shard(local_train, shards: int):
    """``local_train`` run on each of ``shards`` equal device blocks in
    turn, on one chip, with the outputs concatenated: the vmapped program
    at the width each chip of a ``shards``-way "data" mesh runs."""
    def run(*args):
        *dev_args, use_kd = args
        per = dev_args[1].shape[0] // shards
        outs = [local_train(*jax.tree.map(lambda a: a[i * per:(i + 1) * per],
                                          dev_args), use_kd)
                for i in range(shards)]
        return jax.tree.map(lambda *xs: jnp.concatenate(xs), *outs)
    return run


def tree_dev(a, b) -> float:
    return max(max_dev(x, y) for x, y in zip(jax.tree.leaves(a),
                                            jax.tree.leaves(b)))


def phase_mesh(programs):
    """Each sharded run must lie within 1e-6 of a reference that runs the
    same program width per chip.  The TPU compiles a vmap of the SGD step
    to different float results at different widths (2 devices vs 8), and
    200 local steps amplify them (PERF.md), so an unmatched reference
    could not tell a sharding fault from a change of width:

    * trainer: a ``shard_devices`` round on a 4-shard ``"data"`` mesh
      against the vmapped trainer on one chip whose local SGD runs the
      mesh's 2-device blocks one after another; every reduction and the
      server conversion are the one-chip programs;
    * sweep: a (2, 1) ``("grid", "data")`` mesh against each grid point
      swept alone on one chip, and a (2, 2) mesh against each point
      swept alone on a (1, 2) mesh, whose chips hold the same devices and
      psum over the same two shards.
    """
    require(len(jax.devices()) >= 4,
            f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    # 8 devices: the mesh takes the largest divisor of |D| that fits, so
    # 10 devices would silently give 2 shards
    D, shards = 8, 4
    data = dataset(D)
    ch = ChannelConfig(num_devices=D)
    failed = []

    def check(name: str, dev: float) -> float:
        if not dev <= MESH_TOL:
            failed.append(f"{name} {dev!r}")
        return dev

    # -- trainer: shard_devices on a 4-shard "data" mesh --
    def cfg(max_rounds=1, **kw):
        return FederatedConfig(protocol="mix2fld", num_devices=D,
                               max_rounds=max_rounds, **kw)
    tr_s = FederatedTrainer(None, cfg(shard_devices=True), ch)
    tr_w = FederatedTrainer(None, cfg(), ch)
    tr_w._local_train = shard_by_shard(tr_w._local_train, shards)
    require(tr_s.mesh.shape == {"data": shards}, f"trainer mesh {tr_s.mesh}")
    state = tr_w.init_state()
    keys = jax.random.split(jax.random.PRNGKey(0), D)
    lt_args = (state.dev_params, data[0], data[1], keys, state.dev_gout,
               jnp.asarray(False))                  # round 1: no KD yet
    out_s = tr_s._local_train(*lt_args)
    out_w = tr_w._local_train(*lt_args)
    spread = {len(x.sharding.device_set) for x in jax.tree.leaves(out_s)}
    require(spread == {shards}, f"local-train outputs span {spread} devices")

    w = jnp.full((D,), float(data[0].shape[1]))
    ok = jnp.ones((D,))
    red_hlo = (tr_s._weighted_avg.lower(out_w[0], w).compile().as_text() +
               tr_s._gout_update.lower(out_w[1], out_w[2], ok).compile()
               .as_text())
    require("all-reduce" in red_hlo, "no all-reduce in the sharded reductions")
    psum_dev = max(
        tree_dev(tr_s._weighted_avg(out_w[0], w),
                 tr_w._weighted_avg(out_w[0], w)),
        max_dev(tr_s._gout_update(out_w[1], out_w[2], ok),
                tr_w._gout_update(out_w[1], out_w[2], ok)))

    st_s, rec_s = tr_s.round_once(tr_s.init_state(), *data)
    st_w, rec_w = tr_w.round_once(tr_w.init_state(), *data)
    require(rec_s["uplink_ok"] == rec_w["uplink_ok"], "uplink outcomes differ")
    require(np.isfinite(rec_s["acc"]) and np.isfinite(rec_s["loss"]),
            f"non-finite sharded round {rec_s['acc']} {rec_s['loss']}")
    report("mesh_trainer", mesh=dict(tr_s.mesh.shape),
           psum=check("trainer psum", psum_dev),
           local_train=check("trainer local train", tree_dev(out_s, out_w)),
           acc=check("trainer acc", abs(rec_s["acc"] - rec_w["acc"])),
           loss=check("trainer loss", abs(rec_s["loss"] - rec_w["loss"])),
           gout=check("trainer G_out", max_dev(st_s.gout, st_w.gout)),
           global_params=check("trainer global params",
                               tree_dev(st_s.g_params, st_w.g_params)),
           device_params=check("trainer device params",
                               tree_dev(st_s.dev_params, st_w.dev_params)))

    # -- sweep: (2, 1) and (2, 2) ("grid", "data") meshes --
    p_up = (23.0, 40.0)

    def sweep(mesh_shape, *values):
        grid = make_grid(cfg(max_rounds=2), ch, p_up_dbm=values)
        runner = SweepRunner(None, grid, *data,
                             options=ProgramOptions(mesh_shape=mesh_shape))
        shapes = {p.mesh_shape for _, _, p in runner._programs}
        require(shapes == {mesh_shape or None},
                f"sweep mesh shapes {shapes}, want {mesh_shape}")
        return runner.run()

    for mesh_shape, ref_shape in (((2, 1), None), ((2, 2), (1, 2))):
        res = sweep(mesh_shape, *p_up)
        require(np.all(np.isfinite(res.acc)) and
                np.all(np.isfinite(res.loss)), "non-finite mesh sweep")
        acc_dev = loss_dev = 0.0
        for g, value in enumerate(p_up):
            alone = sweep(ref_shape, value)
            require(np.array_equal(res.up_ok[g], alone.up_ok[0]),
                    "uplink outcomes differ")
            acc_dev = max(acc_dev, max_dev(res.acc[g], alone.acc[0]))
            loss_dev = max(loss_dev, max_dev(res.loss[g], alone.loss[0]))
        name = f"sweep {mesh_shape}"
        report("mesh_sweep", mesh=mesh_shape, reference=ref_shape or "1 chip",
               points=len(p_up), acc=check(f"{name} acc", acc_dev),
               loss=check(f"{name} loss", loss_dev))
    require(not failed, f"sharded vs width-matched reference above "
                        f"{MESH_TOL}: {'; '.join(failed)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded mesh path and its "
                         "vmapped reference")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (first device is "
              f"{dev.platform!r}); refusing to fall back", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"[device] platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(jax.devices())} compile_cache={cache}", flush=True)

    programs = ProgramCounter()
    phases = ([phase_mesh] if args.chips == 4 else
              [phase_kernels, phase_trainer, phase_service, phase_sweep])
    for phase in phases:
        t0 = time.perf_counter()
        phase(programs)
        print(f"[{phase.__name__[6:]}] passed in "
              f"{time.perf_counter() - t0:.3f} s", flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
