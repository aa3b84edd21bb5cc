"""The compiled protocol-sweep runner.

``SweepRunner`` turns a :class:`~repro.sweep.axes.SweepGrid` into as few
jitted programs as the grid's structure allows: per-config constants
(step sizes, conversion budgets, link budgets, padded seed sets, PRNG
keys, device partitions) are stacked along a leading grid axis G, the
per-round protocol step from ``repro.core.protocols.make_grid_round_step``
is vmapped over that axis, and ``jax.lax.scan`` drives it over rounds —
so a grid of G configs × D devices × R rounds executes without returning
to Python.  Two axes cannot batch into one program and are handled
structurally instead:

* **protocol** — round bodies differ across protocols (FL aggregates
  models, FD only output tables, the FLD family converts outputs to a
  model), so the runner groups grid points by protocol and compiles ONE
  vmapped scan per distinct protocol (``engine_stats`` counts traces;
  the heterogeneous-grid tests assert program count == #protocols);
* **partition** — points may train on different device partitions
  (``partition``/``alpha``/``n_local`` axes).  Each *distinct*
  :class:`~repro.data.partition.PartitionSpec` is built exactly once,
  ragged ``n_local`` partitions are zero-padded to the grid maximum and
  stacked per-config, and the traced per-config ``n_local`` batch-draw
  bound masks the pad rows (identical draws to the loop path's static
  bound).

With ``shard_devices`` set on the base config, the device axis
additionally runs under ``shard_map`` on the 1-D "data" mesh (the same
placement the trainer uses), composing grid-vmap × device-sharding.

Everything the compiled programs cannot express is absorbed host-side
*before* the scans, in exactly the per-point order the loop path uses:

* round-1 seed collection (sort-based pairing + cycle search) runs once
  per *seed group* via the content-keyed ``core.seed_prep`` memo — the
  key fingerprints the partition, so heterogeneous-partition grids prep
  once per distinct (config fields, partition, key) content, not once
  per point — then pads the ragged train sets to the grid maximum
  (``n_train`` masks the `randint` draws onto the live prefix);
* conversion step keys are precomputed per (round, config) because
  ``jax.random.split`` is not prefix-stable across split counts;
* channel link budgets reduce to per-slot success probabilities and
  decode-slot counts (``round_slot_plan``), so traced draws stay
  bitwise-equal to the loop path.

The sweep-vs-loop equivalence tests (tests/test_sweep.py) assert the
whole per-round history matches ``FederatedTrainer.run`` per grid point,
heterogeneous grids included.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..channel import round_slot_plan
from ..core.privacy import GaussianAccountant, gaussian_epsilon
from ..core.program import GridRoundProgram, ProgramOptions
from ..core.protocols import (FLD_FAMILY, FederatedTrainer,
                              gout_update_psum, make_grid_local_train,
                              make_grid_round_step, weighted_avg_psum)
from ..core.seed_prep import SeedPrepMemo, prepare_seeds
from ..core.state import RoundState
from ..data.pipeline import parse_task
from ..launch.mesh import (_largest_divisor, make_device_mesh,
                           make_grid_mesh)
from ..registry import MODELS, TASKS
from .axes import SweepGrid
from .results import SweepResult


@dataclasses.dataclass
class EngineStats:
    """Trace/lower instrumentation: ``programs`` counts compiled-program
    *builds*, ``traces`` counts actual jit trace events (the counter is a
    Python side effect inside the jitted scan wrapper, so warm calls do
    not increment it).  The heterogeneous-grid tests assert a mixed
    protocol grid traces exactly once per distinct protocol."""
    programs: int = 0
    traces: int = 0

    def reset(self):
        self.programs = 0
        self.traces = 0


engine_stats = EngineStats()


def _pad_seed_sets(seed_sets, num_classes: int):
    """Stack ragged per-config train sets: (G, Nmax, ...) x, (G, Nmax[, C])
    y, (G,) live sizes.  Memoized seed prep hands grid points that share a
    seed key the *same* result object, so padding runs once per unique set
    and the stacked consts are fancy-indexed copies of those rows.  Mixed
    hard/soft grids (e.g. a ``lam`` axis that crosses 0.5) promote hard
    labels to one-hot rows — the conversion losses are identical for
    one-hot targets, so only mixed grids pay the (ulp-level) formulation
    change."""
    uniq_of: dict[int, int] = {}
    uniq, inv = [], []
    for s in seed_sets:
        u = uniq_of.get(id(s))
        if u is None:
            u = uniq_of[id(s)] = len(uniq)
            uniq.append(s)
        inv.append(u)
    xs = [np.asarray(s["train_x"]) for s in uniq]
    ys = [np.asarray(s["train_y"]) for s in uniq]
    n = np.asarray([x.shape[0] for x in xs], np.int32)
    n_max = int(n.max())
    feat = xs[0].shape[1:]
    px = np.zeros((len(xs), n_max) + feat, np.float32)
    for u, x in enumerate(xs):
        px[u, :x.shape[0]] = x
    hard = [y.ndim == 1 for y in ys]
    if all(hard):
        py = np.zeros((len(ys), n_max), np.int32)
        for u, y in enumerate(ys):
            py[u, :y.shape[0]] = y
    else:
        py = np.zeros((len(ys), n_max, num_classes), np.float32)
        for u, y in enumerate(ys):
            if y.ndim == 1:
                y = np.eye(num_classes, dtype=np.float32)[y]
            py[u, :y.shape[0]] = y
    inv = np.asarray(inv)
    return px[inv], py[inv], n[inv]


def _stack_partitions(parts):
    """Stack the per-point device partitions of one protocol group.

    ``parts``: list of (dev_x, dev_y) pairs, one per point — points
    sharing a :class:`PartitionSpec` share the *same* array objects, so
    identity dedup keeps padding O(#distinct partitions).  Returns
    ``(dev_x, dev_y, n_local (G,), per_config)``: a group whose points
    all train on one partition keeps the single (D, n, ...) arrays
    (``per_config=False``, the classic homogeneous layout); otherwise
    ragged ``n_local`` partitions are zero-padded to the group maximum
    and stacked to (G, D, Nmax, ...).  Pad rows are never sampled: the
    traced per-config ``n_local`` bounds every batch draw."""
    n_local = np.asarray([x.shape[1] for x, _ in parts], np.int32)
    if len({id(x) for x, _ in parts}) == 1:
        x, y = parts[0]
        return jnp.asarray(x), jnp.asarray(y), n_local, False
    uniq_of: dict[int, int] = {}
    uniq, inv = [], []
    for pair in parts:
        u = uniq_of.get(id(pair[0]))
        if u is None:
            u = uniq_of[id(pair[0])] = len(uniq)
            uniq.append(pair)
        inv.append(u)
    xs = [np.asarray(x) for x, _ in uniq]
    ys = [np.asarray(y) for _, y in uniq]
    n_max = int(max(x.shape[1] for x in xs))
    D = xs[0].shape[0]
    feat = xs[0].shape[2:]
    px = np.zeros((len(xs), D, n_max) + feat, np.float32)
    py = np.zeros((len(ys), D, n_max), ys[0].dtype)
    for u, (x, y) in enumerate(zip(xs, ys)):
        px[u, :, :x.shape[1]] = x
        py[u, :, :y.shape[1]] = y
    inv = np.asarray(inv)
    return jnp.asarray(px[inv]), jnp.asarray(py[inv]), n_local, True


def _resolve_partitions(grid: SweepGrid, dev_x, dev_y, num_devices: int,
                        num_classes: int):
    """Per-point (dev_x, dev_y) pairs.  Partitioned grids build each
    distinct :class:`PartitionSpec` exactly once from the flat sample
    pool; classic grids share the given pre-partitioned arrays (one
    object, so downstream identity dedup and the seed-prep fingerprint
    cache both see a single partition)."""
    if dev_x is None or dev_y is None:
        raise ValueError(
            "grid without a task axis takes explicit data: pass "
            "dev_x/dev_y (and test_x/test_y), or task_data=... / "
            "make_task_data(grid) to draw the base task's procedural "
            "pool")
    if grid.partitioned:
        pool_x, pool_y = np.asarray(dev_x), np.asarray(dev_y)
        if pool_y.ndim != 1:
            raise ValueError(
                "grids with partition axes take the flat sample pool "
                f"(x (N, ...), y (N,)); got y shape {pool_y.shape} — "
                "pass the unpartitioned data and let each point's "
                "PartitionSpec split it")
        built: dict = {}
        for spec in grid.parts:
            if spec not in built:
                built[spec] = spec.build(pool_x, pool_y, num_devices,
                                         num_classes)
        return [built[spec] for spec in grid.parts]
    if np.asarray(dev_y).ndim != 2:
        raise ValueError(
            "grids without partition axes take pre-partitioned "
            f"(D, n_local) data; got dev_y shape "
            f"{np.asarray(dev_y).shape}")
    shared = (dev_x, dev_y)
    return [shared] * grid.size


def make_task_data(grid: SweepGrid, n_test: int = 200,
                   data_seed: int = 1234) -> dict:
    """Materialize one procedural sample pool + test set per distinct
    task of a tasked grid: ``{task: (pool_x, pool_y, test_x, test_y)}``.

    Pools are sized for the largest partition any point of the task
    requests (``num_devices * n_local``), drawn from a per-task fold of
    ``data_seed`` so every task's data is deterministic and independent
    of grid layout.  Pass the result (or your own dict with the same
    layout) to :class:`SweepRunner` / :func:`run_pointwise` as
    ``task_data``."""
    out = {}
    for task, idxs in grid.task_groups().items():
        spec = parse_task(task)
        fc0 = grid.points[idxs[0]][0]
        if not grid.partitioned:
            raise ValueError("make_task_data needs a partitioned grid "
                             "(task axes always are)")
        n_pool = max(grid.points[g][0].num_devices * grid.parts[g].n_local
                     for g in idxs)
        key = jax.random.fold_in(jax.random.PRNGKey(data_seed),
                                 TASKS.index(spec.name))
        x, y = spec.data(key, n_pool + n_test, fc0.num_classes)
        out[task] = (np.asarray(x[:n_pool]), np.asarray(y[:n_pool]),
                     np.asarray(x[n_pool:]), np.asarray(y[n_pool:]))
    return out


def _resolve_task_partitions(grid: SweepGrid, task_data: dict):
    """Per-point (dev_x, dev_y) pairs for a tasked grid: each distinct
    (task, PartitionSpec) pair is built exactly once from that task's
    pool (identity-shared arrays keep the seed-prep fingerprint cache
    and stacking dedup effective)."""
    missing = set(grid.task_groups()) - set(task_data)
    if missing:
        raise ValueError(f"task_data is missing pools for {sorted(missing)}")
    built: dict = {}
    parts = []
    for (fc, _), spec in zip(grid.points, grid.parts):
        key = (fc.task, spec)
        if key not in built:
            px, py = task_data[fc.task][:2]
            built[key] = spec.build(px, py, fc.num_devices, fc.num_classes)
        parts.append(built[key])
    return parts


def _group_models(model, fc):
    """Resolve one program group's models: the caller-supplied object for
    classic grids, else registry builds from the group's (model, task)
    identity.  Returns ``(global_model, arch_models)`` where
    ``arch_models`` is None for homogeneous cohorts or the ordered
    ``[(name, device_indices, model), ...]`` groups (first group =
    server architecture = device 0, the round-robin contract the grid
    step relies on)."""
    if model is not None:
        return model, None
    models = fc.build_models()
    groups = fc.arch_groups()
    gmodel = models[fc.server_model()]
    if groups is None:
        return gmodel, None
    if groups[0][0] != fc.server_model():
        raise ValueError(
            "grid programs require device 0 to run the server "
            f"architecture ({fc.server_model()!r}); the partition "
            f"starts with {groups[0][0]!r}")
    return gmodel, [(a, idx, models[a]) for a, idx in groups]


class _ProtocolProgram:
    """One compiled program: every grid point of one protocol.  This is
    the stacking/tracing core the homogeneous runner used to be, now
    scoped to a protocol group (``idxs``, in grid order) with per-config
    partitions."""

    def __init__(self, model, grid: SweepGrid, proto: str, idxs, parts,
                 test_x, test_y, memo: SeedPrepMemo, mesh,
                 codec: str = "identity", cohort_size: int | None = None,
                 arch_models: list | None = None,
                 options: ProgramOptions | None = None):
        engine_stats.programs += 1
        fc0, ch0 = grid.points[idxs[0]]
        self.idxs = idxs
        self.codec = codec
        self.options = options or ProgramOptions()
        points = [grid.points[i] for i in idxs]
        G, D, C, R = len(idxs), fc0.num_devices, fc0.num_classes, \
            fc0.max_rounds
        # client sampling: the cohort size is part of this group's
        # structural identity (program_groups), so every point agrees
        Dc = D if cohort_size is None else min(int(cohort_size), D)
        sampled = Dc < D
        dev_x, dev_y, n_local, per_config = _stack_partitions(parts)
        feat = dev_x.shape[3:] if per_config else dev_x.shape[2:]
        if self.options.mesh_shape is not None:
            # pod-scale 2-D (grid x device) mesh: this group's G points
            # lay out along "grid", each point's cohort along "data".
            # The requested shape is a *budget* — each program group
            # re-fits it to its own grid slice (the largest divisors
            # that fit the request AND the local chip count), so a
            # 5-point group on a 2x4 request, or a 2x4 request on a
            # 1-chip host, still shards what it can instead of erroring.
            avail = len(jax.devices())
            gs = _largest_divisor(G, min(self.options.mesh_shape[0],
                                         avail))
            ds = _largest_divisor(Dc, min(self.options.mesh_shape[1],
                                          avail // gs))
            mesh = make_grid_mesh(G, Dc, shape=(gs, ds))
        elif sampled and mesh is not None:
            # the mesh spans the cohort (only Dc devices enter the
            # shard_mapped fns), mirroring the sampled trainer's mesh
            mesh = make_device_mesh(Dc, fc0.mesh_shards or None)
        self.mesh_shape = (tuple(mesh.devices.shape)
                           if mesh is not None else None)

        # ---- host prep, per config in the loop path's exact key order;
        # seed prep is memoized on the seed-determining content (config
        # fields + partition fingerprint + key bytes), so points sharing
        # a seed key — and, across partitions, distinct points sharing
        # one partition's content — share one result object ----
        run_keys, inits, conv_keys, seed_sets = [], [], [], []
        # mixed cohorts: per-point inits for the non-server architectures
        # (the server architecture's group shares the global init, the
        # same stream contract as FederatedTrainer.init_state)
        arch_inits = {a: [] for a, _, _ in (arch_models or [])[1:]}
        plans = {"p_up": [], "p_dn": [], "up1": [], "up": [], "dn": [],
                 "up_bits1": [], "up_bits": []}
        specs = [fc.codec_spec() for fc, _ in points]
        k_max = max(fc.server_iters for fc, _ in points)
        # sampled groups prep seeds on the round-1 *cohort* slice of each
        # partition (the loop path collects from the gathered cohort);
        # gathers are cached by (partition identity, cohort content) so
        # points sharing both still share one array object — keeping the
        # seed-prep memo's identity/fingerprint dedup effective
        gather_cache: dict = {}
        for (fc, ch), spec, (px, py) in zip(points, specs, parts):
            kinit, key = jax.random.split(jax.random.PRNGKey(fc.seed))
            run_keys.append(np.asarray(key))
            params = model.init(kinit)
            inits.append(params)
            for a, _, m in (arch_models or [])[1:]:
                arch_inits[a].append(m.init(
                    jax.random.fold_in(kinit, MODELS.index(a) + 1)))
            n_mod = sum(p.size for p in jax.tree.leaves(params))
            if proto in FLD_FAMILY:
                spx, spy = px, py
                if sampled:
                    c1 = fc.sampler().cohort(fc.seed, 1, D)
                    ckey = (id(px), c1.tobytes())
                    pair = gather_cache.get(ckey)
                    if pair is None:
                        pair = (np.asarray(px)[c1], np.asarray(py)[c1])
                        gather_cache[ckey] = pair
                    spx, spy = pair
                kr1 = jax.random.fold_in(key, 1)
                seed_sets.append(prepare_seeds(
                    fc, spx, spy, jax.random.fold_in(kr1, 2), memo=memo))
                ck = np.zeros((R, k_max, 2), np.uint32)
                for p in range(1, R + 1):
                    base = jax.random.fold_in(jax.random.fold_in(key, p), 4)
                    ck[p - 1, :fc.server_iters] = np.asarray(
                        jax.random.split(base, fc.server_iters))
                conv_keys.append(ck)
            plan = round_slot_plan(
                proto, ch, n_mod=n_mod, n_labels=C,
                sample_bits=fc.sample_bits, n_seed=fc.n_seed, codec=spec)
            plans["p_up"].append(plan["p_up"])
            plans["p_dn"].append(plan["p_dn"])
            plans["up1"].append(plan["up_slots_first"])
            plans["up"].append(plan["up_slots"])
            plans["dn"].append(plan["dn_slots"])
            plans["up_bits1"].append(plan["up_bits_first"])
            plans["up_bits"].append(plan["up_bits"])

        g_params = jax.tree.map(lambda *ls: jnp.stack(ls), *inits)
        n_params = sum(p[0].size for p in jax.tree.leaves(g_params))

        consts = {
            "key": jnp.asarray(np.stack(run_keys)),
            "eta": jnp.asarray([fc.eta for fc, _ in points], jnp.float32),
            "beta": jnp.asarray([fc.beta for fc, _ in points],
                                jnp.float32),
            "s_iters": jnp.asarray(
                [fc.server_iters for fc, _ in points], jnp.int32),
            "eps": jnp.asarray([fc.eps for fc, _ in points], jnp.float32),
            "n_local": jnp.asarray(n_local),
            "p_up": jnp.asarray(plans["p_up"], jnp.float32),
            "p_dn": jnp.asarray(plans["p_dn"], jnp.float32),
        }
        if codec != "identity":
            # codec numeric parameters batch as traced per-config scalars
            # (the codec *family* is this program's structural identity)
            consts["q_levels"] = jnp.asarray(
                [s.levels for s in specs], jnp.float32)
            consts["dp_sigma"] = jnp.asarray(
                [s.dp_sigma for s in specs], jnp.float32)
            consts["dp_clip"] = jnp.asarray(
                [s.dp_clip for s in specs], jnp.float32)

        # per-point link accounting for result frames (host floats; the
        # bits -> slots mapping already shaped the compiled plans above)
        self.up_bits_first = np.asarray(plans["up_bits1"], np.float64)
        self.up_bits_steady = np.asarray(plans["up_bits"], np.float64)
        self.dp_epsilon = np.asarray(
            [gaussian_epsilon(s.dp_sigma, s.dp_delta, R)
             if s.name == "dp_gaussian" else np.nan for s in specs])
        # full DP ledgers, participation-aware: stepped through the same
        # accountant (with the same per-round cohorts) the loop path's
        # run() uses, so sweep histories carry identical history["dp"]
        self.dp_ledgers = []
        for (fc, _), s in zip(points, specs):
            if s.name != "dp_gaussian":
                self.dp_ledgers.append(None)
                continue
            acct = GaussianAccountant(s.dp_sigma, s.dp_delta,
                                      sample_ratio=fc.sample_ratio)
            smp = fc.sampler()
            for p in range(1, R + 1):
                acct.step(cohort=(smp.cohort(fc.seed, p, D) if sampled
                                  else None))
            self.dp_ledgers.append(acct.ledger())
        self.dp_epsilon_device = np.asarray(
            [led["epsilon_device_max"] if led else np.nan
             for led in self.dp_ledgers])
        if proto in FLD_FAMILY:
            sx, sy, n_train = _pad_seed_sets(seed_sets, C)
            consts["seeds_x"] = jnp.asarray(sx)
            consts["seeds_y"] = jnp.asarray(sy)
            consts["n_train"] = jnp.asarray(n_train)
            ck = jnp.asarray(np.stack(conv_keys, axis=1))  # (R, G, Kmax, 2)
        else:
            consts["seeds_x"] = jnp.zeros((G, 1) + feat)
            consts["seeds_y"] = jnp.zeros((G, 1), jnp.int32)
            consts["n_train"] = jnp.ones((G,), jnp.int32)
            ck = jnp.zeros((R, G, 1, 2), jnp.uint32)

        up_slots = np.tile(np.asarray(plans["up"], np.int32), (R, 1))
        up_slots[0] = np.asarray(plans["up1"], np.int32)
        self._xs = {
            "p": jnp.arange(1, R + 1, dtype=jnp.int32),
            "up_slots": jnp.asarray(up_slots),
            "dn_slots": jnp.tile(jnp.asarray(plans["dn"], jnp.int32)[None],
                                 (R, 1)),
            "conv_keys": ck,
        }
        if sampled:
            # every round's cohort, host-drawn per point: (R, G, Dc)
            # gather indices for the compiled scan (unsampled groups get
            # no "cohort" input at all — graph-identical to the classic
            # step)
            cohorts = np.stack([
                np.stack([fc.sampler().cohort(fc.seed, p, D)
                          for fc, _ in points])
                for p in range(1, R + 1)])
            self._xs["cohort"] = jnp.asarray(cohorts, jnp.int32)

        # ---- device-axis placement: vmapped, or shard_mapped over the
        # "data" mesh exactly like the trainer's sharded path ----
        fns = {}
        if mesh is not None:
            # a sampled gather hands local_train per-config (G, Dc, ...)
            # batches even off shared data, so the in_axes/in_specs
            # follow the per-config layout whenever sampling is on
            grid_lt = make_grid_local_train(model.apply, C,
                                            fc0.local_iters,
                                            fc0.local_batch,
                                            per_config or sampled)
            # on a 2-D ("grid", "data") mesh the (G, D, ...) state shards
            # both axes and the per-config (G,) scalars shard "grid";
            # every reduction stays a psum over "data" only, so each grid
            # shard's collective spans exactly its own points' device
            # rows — no cross-point communication is introduced.  On the
            # 1-D ("data",) mesh gcfg degrades to P() (replicated),
            # recovering the previous specs verbatim.
            grid_axis = "grid" in mesh.axis_names
            gdev = P("grid", "data") if grid_axis else P(None, "data")
            gcfg = P("grid") if grid_axis else P()
            ddev = gdev if (per_config or sampled) else P("data")
            rep = P()
            fns["local_train_fn"] = shard_map(
                grid_lt, mesh=mesh,
                in_specs=(gdev, ddev, ddev, gdev, gdev, rep, gcfg, gcfg,
                          gcfg),
                out_specs=(gdev, gdev, gdev, gdev), check_vma=False)
            fns["weighted_avg_fn"] = shard_map(
                jax.vmap(weighted_avg_psum), mesh=mesh,
                in_specs=(gdev, gdev), out_specs=gcfg, check_vma=False)
            fns["gout_update_fn"] = shard_map(
                jax.vmap(gout_update_psum), mesh=mesh,
                in_specs=(gdev, gdev, gdev), out_specs=gcfg,
                check_vma=False)
            if grid_axis:
                # conversion and evaluation: each grid shard runs its own
                # points, replicated over "data" (the compiler would
                # otherwise gather them to full grid width on every chip)
                fns["grid_shard"] = lambda f: shard_map(
                    f, mesh=mesh, in_specs=gcfg, out_specs=gcfg,
                    check_vma=False)

        round_step = make_grid_round_step(
            model.apply, protocol=proto, num_devices=D,
            num_classes=C, local_iters=fc0.local_iters,
            local_batch=fc0.local_batch, server_batch=fc0.server_batch,
            t_max_slots=ch0.t_max_slots, tau_s=ch0.tau_s,
            dev_x=dev_x, dev_y=dev_y, test_x=jnp.asarray(test_x),
            test_y=jnp.asarray(test_y), consts=consts,
            per_config_data=per_config, codec=codec,
            cohort_size=Dc,
            arch_groups=(None if arch_models is None else
                         [(a, idx, m.apply) for a, idx, m in arch_models]),
            **fns)

        def _sweep_program(state, xs):
            engine_stats.traces += 1  # Python side effect: trace-counted
            return jax.lax.scan(round_step, state, xs)

        self._step_fn = jax.jit(_sweep_program)

        if arch_models is None:
            dev_params0 = jax.tree.map(
                lambda p: jnp.broadcast_to(
                    p[:, None], (G, D) + p.shape[1:]).copy(), g_params)
        else:
            # per-architecture (G, Da, ...) stacks; group 0 (= device 0 =
            # server architecture) broadcasts the global init
            dev_params0 = {}
            for a, idx, _ in arch_models:
                base = (g_params if a == arch_models[0][0] else
                        jax.tree.map(lambda *ls: jnp.stack(ls),
                                     *arch_inits[a]))
                dev_params0[a] = jax.tree.map(
                    lambda p: jnp.broadcast_to(
                        p[:, None], (G, len(idx)) + p.shape[1:]).copy(),
                    base)
        self._state0 = RoundState(
            dev_params=dev_params0,
            g_params=g_params,
            gout=jnp.full((G, C, C), 1.0 / C),
            dev_gout=jnp.full((G, D, C, C), 1.0 / C),
            prev=jnp.zeros((G, C * C if proto == "fd" else n_params)),
            converged_round=jnp.zeros((G,), jnp.int32),
            # host-loop fields ride as None in the grid layout
            round=None, key=None, seeds=None, cum_time_s=None)
        self._rp = GridRoundProgram(self._step_fn, self._state0,
                                    options=self.options)
        self.seed_sets = seed_sets if proto in FLD_FAMILY else None

    def run(self):
        """Execute the compiled scan through the :class:`GridRoundProgram`
        face; returns (final state, per-round outputs), outputs stacked
        (R, Gp)."""
        self._rp.step(self._state0, self._xs)
        return self._rp.finalize()


class SweepRunner:
    """Compiles one grid into at most one program per distinct protocol;
    ``run()`` re-executes the same compiled scans (warm calls skip
    tracing and compilation).  Heterogeneous grids (protocol and/or
    partition axes) and classic single-protocol shared-partition grids
    take the same entry point — for partitioned grids pass the *flat*
    sample pool as ``dev_x``/``dev_y`` and each point's
    :class:`PartitionSpec` splits it.

    Model/task-structural grids pass ``model=None``: each program group
    builds its architecture(s) from the model registry at the group's
    task shape, and grids with a ``task`` axis generate per-task
    procedural pools/test sets (``task_data``, auto-generated via
    :func:`make_task_data` when not given) instead of taking
    ``dev_x``/``test_x``."""

    def __init__(self, model, grid: SweepGrid, dev_x=None, dev_y=None,
                 test_x=None, test_y=None, *, task_data=None,
                 options: ProgramOptions | None = None):
        fc0, ch0 = grid.points[0]
        self.options = options or ProgramOptions()
        if ch0.num_devices != fc0.num_devices:
            raise ValueError(
                f"channel simulates {ch0.num_devices} links but the "
                f"population has {fc0.num_devices} devices")
        if model is not None and (
                grid.tasked
                or len({fc.model_key() for fc, _ in grid.points}) > 1
                or any(fc.model_partition is not None
                       for fc, _ in grid.points)):
            raise ValueError(
                "grids that sweep model/task axes (or run mixed-"
                "architecture cohorts) build their models from the "
                "registry; pass model=None")
        self.model = model
        self.grid = grid
        D, C = fc0.num_devices, fc0.num_classes

        if grid.tasked or task_data is not None:
            if dev_x is not None or dev_y is not None or \
                    test_x is not None or test_y is not None:
                raise ValueError(
                    "task-driven grids generate per-task pools and test "
                    "sets; pass dev_x/dev_y/test_x/test_y=None (supply "
                    "task_data=... to override the generated data)")
            if task_data is None:
                task_data = make_task_data(grid)
            self.task_data = task_data
            self.partitions = _resolve_task_partitions(grid, task_data)
        else:
            self.task_data = None
            self.partitions = _resolve_partitions(grid, dev_x, dev_y, D, C)

        self.mesh = (make_device_mesh(D, fc0.mesh_shards or None)
                     if fc0.shard_devices else None)

        memo = SeedPrepMemo()
        self._programs = []          # (protocol, idxs, program)
        for (proto, codec, csize, modelk, task), idxs in \
                grid.program_groups().items():
            fcg = grid.points[idxs[0]][0]
            gmodel, arch_models = _group_models(model, fcg)
            if self.task_data is not None:
                gtx, gty = self.task_data[task][2:4]
            else:
                gtx, gty = test_x, test_y
            prog = _ProtocolProgram(
                gmodel, grid, proto, idxs,
                [self.partitions[i] for i in idxs],
                gtx, gty, memo, self.mesh, codec=codec,
                cohort_size=csize, arch_models=arch_models,
                options=self.options)
            self._programs.append((proto, idxs, prog))
        self.programs = len(self._programs)

        self.seed_memo = memo
        fld_pts = [g for g, (fc, _) in enumerate(grid.points)
                   if fc.protocol in FLD_FAMILY]
        self.seed_prep_stats = {
            "groups": len({grid.seed_key(g) for g in fld_pts}),
            "prep_runs": memo.misses,
            "memo_hits": memo.hits,
        }
        if fld_pts:  # per-point seed sets in grid order (None at fl/fd
            # points of a mixed grid; dense for classic all-FLD grids)
            self.seed_sets = [None] * grid.size
            for _, idxs, prog in self._programs:
                if prog.seed_sets is not None:
                    for i, s in zip(idxs, prog.seed_sets):
                        self.seed_sets[i] = s
        else:
            self.seed_sets = None

    # ------------------------------------------------------------------
    def run(self) -> SweepResult:
        G, R = self.grid.size, self.grid.points[0][0].max_rounds
        acc = np.zeros((G, R), np.float32)
        loss = np.zeros((G, R), np.float32)
        latency = np.zeros((G, R), np.float64)
        up_ok = np.zeros((G, R), np.int32)
        converged = np.zeros((G,), np.int32)
        up_bits_first = np.zeros((G,), np.float64)
        up_bits = np.zeros((G,), np.float64)
        dp_epsilon = np.full((G,), np.nan)
        dp_epsilon_device = np.full((G,), np.nan)
        dp = [None] * G
        t0 = time.perf_counter()
        for proto, idxs, prog in self._programs:
            rows = np.asarray(idxs)
            with jax.profiler.TraceAnnotation("sweep_group",
                                              points=len(idxs), rounds=R):
                state, out = prog.run()
                converged[rows] = np.asarray(state["converged"])
            acc[rows] = out["acc"].T
            loss[rows] = out["loss"].T
            latency[rows] = out["latency_s"].T.astype(np.float64)
            up_ok[rows] = out["up_ok"].T
            up_bits_first[rows] = prog.up_bits_first
            up_bits[rows] = prog.up_bits_steady
            dp_epsilon[rows] = prog.dp_epsilon
            dp_epsilon_device[rows] = prog.dp_epsilon_device
            for i, led in zip(idxs, prog.dp_ledgers):
                dp[i] = led
        wall = time.perf_counter() - t0
        return SweepResult(
            grid=self.grid, acc=acc, loss=loss, latency_s=latency,
            up_ok=up_ok, converged=converged, wall_s=wall,
            up_bits_first=up_bits_first, up_bits=up_bits,
            dp_epsilon=dp_epsilon, dp_epsilon_device=dp_epsilon_device,
            dp=tuple(dp))


def run_sweep(model, grid: SweepGrid, dev_x=None, dev_y=None, test_x=None,
              test_y=None, *, task_data=None,
              options: ProgramOptions | None = None) -> SweepResult:
    """One-shot convenience: build a :class:`SweepRunner` and run it."""
    return SweepRunner(model, grid, dev_x, dev_y, test_x, test_y,
                       task_data=task_data, options=options).run()


def run_pointwise(model, grid: SweepGrid, dev_x=None, dev_y=None,
                  test_x=None, test_y=None, log=None, *,
                  task_data=None) -> list[dict]:
    """The per-point loop the sweep replaces (and the equivalence oracle):
    one ``FederatedTrainer.run`` per grid point, re-tracing each time.
    Partitioned grids build each point's partition exactly like the
    runner, task-driven grids draw the same per-task pools/test sets, and
    ``model=None`` points build their (possibly mixed) architectures from
    the registry — so histories are comparable point-for-point."""
    fc0 = grid.points[0][0]
    if grid.tasked or task_data is not None:
        if task_data is None:
            task_data = make_task_data(grid)
        parts = _resolve_task_partitions(grid, task_data)
        tests = [task_data[fc.task][2:4] for fc, _ in grid.points]
    else:
        parts = _resolve_partitions(grid, dev_x, dev_y, fc0.num_devices,
                                    fc0.num_classes)
        tests = [(test_x, test_y)] * grid.size
    return [FederatedTrainer(model, fc, ch).run(px, py, tx, ty, log=log)
            for (fc, ch), (px, py), (tx, ty)
            in zip(grid.points, parts, tests)]
