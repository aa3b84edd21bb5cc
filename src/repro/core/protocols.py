"""Protocol engines: FL, FD, FLD, MixFLD, Mix2FLD (Algorithm 1).

The federated population is simulated exactly as in Sec. II: per-round
local SGD at every device, Rayleigh-faded uplink/downlink with SNR-gated
success, weighted aggregation over the successful set, and — for the FLD
family — the server-side output-to-model conversion of eq. (5).

Device-side math is jitted over the device axis on one of two paths,
selected by ``FederatedConfig.shard_devices``:

* **vmapped** (default) — the whole population on one chip, the 1-chip
  fallback and the equivalence oracle for the sharded path;
* **mesh-sharded** — the device axis is placed along the "data" axis of a
  1-D mesh (launch.mesh.make_device_mesh) and local SGD runs under
  ``shard_map`` (per-shard vmap over the local device slice); the
  cross-device reductions (weighted model average, the eq. 2 output
  average) are psum collectives, so multi-chip hosts scale the population
  with the chip count.

``FederatedTrainer.run`` keeps a host-side round loop (it mixes channel
sampling, convergence checks and tic-toc compute timing, as the paper
does).  The per-round math itself is factored into pure module-level
pieces — :func:`make_local_train`, :func:`weighted_avg`,
:func:`gout_update`, :func:`collect_seeds` — which
:func:`make_grid_round_step` recombines into a fully-traced round step
batched over a leading *config-grid* axis: the protocol-sweep engine
(``repro.sweep``) scans it over rounds so a whole hyperparameter grid
runs as one compiled program.  The sweep-vs-loop equivalence tests in
tests/test_sweep.py lock the two formulations together.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from ..channel import ChannelConfig
from ..channel.payload import CodecSpec, LinkConfig, parse_codec
from ..channel.pipeline import (LinkPlan, channel_stage, downlink_gout,
                                downlink_params, make_uplink_stage,
                                uplink_stage)
from ..data.pipeline import TaskSpec, parse_task
from ..launch.mesh import make_device_mesh
from ..launch.sharding import federated_pspecs
from ..models.registry import ModelSpec, build_model, parse_model
# the protocol registry is the single source of truth for names; the
# historical PROTOCOLS / FLD_FAMILY module attributes stay as re-exports
from ..registry import (FLD_FAMILY, MODELS, PROTOCOLS,  # noqa: F401
                        canonical_model, canonical_protocol)
from .conversion import output_to_model, output_to_model_steps
from .losses import fd_loss
from .outputs import label_averaged_outputs
from .privacy import GaussianAccountant
from .program import LoopRoundProgram, ProgramOptions, tree_nbytes
from .sampling import ChurnConfig, SamplerConfig
from .state import RoundState
from .seed_prep import (collect_seeds, prepare_seeds,  # noqa: F401
                        summarize_seeds)


@dataclasses.dataclass
class FederatedConfig:
    protocol: str = "mix2fld"
    num_devices: int = 10          # |D|
    num_classes: Optional[int] = None  # N_L (None: the task's class count;
    #                                the registered digits task keeps the
    #                                paper's 10)
    local_iters: int = 200         # K   (paper: 6400 single-sample SGD)
    local_batch: int = 16          # samples per local SGD iteration
    server_iters: int = 160        # K_s (paper: 3200)
    server_batch: int = 16
    eta: float = 0.01
    beta: float = 0.01
    eps: float = 0.05
    lam: float = 0.1               # Mixup ratio
    n_seed: int = 10               # N_S per device
    n_inverse: int = 20            # N_I per device-equivalent (>= N_S)
    max_rounds: int = 20
    sample_bits: Optional[int] = None  # per-sample uplink payload (None:
    #                                the task's width; digits keeps the
    #                                paper's b_s = 8 bit * 28 * 28 = 6272)
    seed: int = 0
    shard_devices: bool = False    # mesh-shard the device axis (False: vmap)
    mesh_shards: int = 0           # 0 = auto (largest divisor of |D| that
    #                                fits the local chip count)
    keep_seed_arrays: bool = False  # opt-in: keep the full round-1 seed
    #                                arrays on history["seed_arrays"]
    #                                (histories otherwise carry only the
    #                                summarize_seeds metadata)
    codec: str = "identity"        # link codec: family name or spec string
    #                                ("quantize8", "dp_gaussian0.5") from
    #                                the channel.payload registry
    quant_bits: int = 8            # quantize codec: bits per element
    dp_sigma: float = 1.0          # dp_gaussian codec: noise multiplier
    dp_clip: float = 1.0           # dp_gaussian codec: L2 sensitivity clip
    dp_delta: float = 1e-5         # dp_gaussian codec: DP delta
    sample_ratio: float = 1.0      # per-round participation fraction q:
    #                                each round trains a seeded cohort of
    #                                ceil(q * num_devices) devices out of
    #                                the num_devices pool (1.0: everyone,
    #                                the paper's setting)
    sample_seed: int = 0           # cohort-draw stream seed (cohorts are
    #                                a pure function of (seed, sample_seed,
    #                                round) — see core.sampling)
    sample_min_active: int = 1     # cohort-size floor
    model: str = "cnn"             # registry model spec — a single
    #                                architecture, or a "+"-joined cohort
    #                                ("cnn+mlp+transformer") assigned to
    #                                devices round-robin (FD family only)
    task: str = "digits"           # registry task: input shape, default
    #                                class count, per-sample payload bits
    model_partition: Optional[tuple] = None  # explicit per-device
    #                                architecture names (len num_devices);
    #                                None: derived from a composite
    #                                ``model`` by cycling its parts
    # -- typed sub-configs (the canonical surface; the flat fields above
    #    are deprecated aliases kept for one release — see _sync_sub) --
    sampler: Optional[SamplerConfig] = None  # client sampling; None:
    #                                built from the sample_* aliases
    churn: Optional[ChurnConfig] = None  # device churn (read by
    #                                launch.service); None: no churn
    channel: Optional[LinkConfig] = None  # link codec; None: built from
    #                                the codec/quant/dp_* aliases.  (The
    #                                *physical* channel stays a separate
    #                                ChannelConfig argument.)

    #: flat alias -> sub-config attribute, per sub-config field.  The
    #: sub-config class defaults double as the flat-field defaults, so
    #: "was this flat alias set?" never needs a second defaults table.
    _SUB_ALIASES = {
        "sampler": (SamplerConfig, {"sample_ratio": "sample_ratio",
                                    "sample_seed": "seed",
                                    "sample_min_active": "min_active"}),
        "channel": (LinkConfig, {"codec": "codec",
                                 "quant_bits": "quant_bits",
                                 "dp_sigma": "dp_sigma",
                                 "dp_clip": "dp_clip",
                                 "dp_delta": "dp_delta"}),
    }

    def _sync_sub(self, attr: str) -> None:
        """Reconcile one typed sub-config with its flat aliases.

        Resolution order (one constructor path for old and new callers):

        * sub-config absent, aliases at defaults — build the default sub;
        * sub-config absent, aliases set — the legacy kwargs path: build
          the sub from the aliases and emit a DeprecationWarning;
        * sub-config present, aliases at defaults — canonical path; the
          aliases are synced *from* the sub so legacy readers
          (``seed_fields_key``'s getattr, sweep axis validation, tests)
          keep seeing live values;
        * both set and disagreeing — the flat aliases win and the sub is
          rebuilt.  This keeps ``dataclasses.replace(fc, sample_ratio=q)``
          (the sweep-axis mutation surface) working on configs that
          already carry sub-configs: replace hands the old sub plus the
          new flat value, and the flat edit must take effect.  Known
          limit: replace() on an alias-set config can't also swap that
          group's sub-config wholesale — set the aliases instead until
          they are removed.

        Validation itself lives in the sub-config ``__post_init__``s —
        the one site either path funnels through.
        """
        cls, aliases = self._SUB_ALIASES[attr]
        defaults = cls()
        sub = getattr(self, attr)
        flats = {f: getattr(self, f) for f in aliases}
        flats_set = any(flats[f] != getattr(defaults, aliases[f])
                        for f in aliases)
        if sub is None:
            if flats_set:
                warnings.warn(
                    f"flat FederatedConfig fields "
                    f"{sorted(f for f in aliases if flats[f] != getattr(defaults, aliases[f]))} "
                    f"are deprecated; pass {attr}={cls.__name__}(...) "
                    f"instead", DeprecationWarning, stacklevel=4)
            sub = cls(**{aliases[f]: flats[f] for f in aliases})
        elif flats_set and \
                any(flats[f] != getattr(sub, aliases[f]) for f in aliases):
            sub = cls(**{aliases[f]: flats[f] for f in aliases})
        object.__setattr__(self, attr, sub)
        for f in aliases:  # aliases mirror the sub-config, always
            object.__setattr__(self, f, getattr(sub, aliases[f]))

    def __post_init__(self):
        # data-dependent bounds (n_seed vs the per-device sample count)
        # are checked where the data is first seen: seed_prep.collect_seeds
        self.protocol = canonical_protocol(self.protocol)
        self.task = parse_task(self.task).name
        if self.num_classes is None:
            self.num_classes = self.task_spec().num_classes
        if self.sample_bits is None:
            self.sample_bits = self.task_spec().sample_bits
        # typed sub-configs reconcile (and validate) before any check
        # below reads a sampling/codec value through either surface
        self._sync_sub("sampler")
        self._sync_sub("channel")
        if self.churn is not None and not isinstance(self.churn,
                                                     ChurnConfig):
            raise TypeError(f"churn must be a ChurnConfig, "
                            f"got {type(self.churn).__name__}")
        mspec = parse_model(self.model)
        self.model = mspec.name
        if self.model_partition is None:
            if mspec.mixed:
                self.model_partition = mspec.partition(self.num_devices)
        else:
            part = tuple(canonical_model(m) for m in self.model_partition)
            if len(part) != self.num_devices:
                raise ValueError(
                    f"model_partition has {len(part)} entries for "
                    f"num_devices={self.num_devices}")
            # a uniform partition of the (single) model is just the
            # homogeneous cohort — normalize so program identity is stable
            self.model_partition = (
                None if set(part) == {self.model} else part)
        if self.model_partition is not None:
            # mixed cohorts exchange *outputs*: only the FD-family uplink
            # aggregates in the shared (C, C) output space
            if self.protocol == "fl":
                raise ValueError(
                    "protocol 'fl' aggregates parameter vectors and "
                    "cannot mix architectures; a mixed-model cohort "
                    f"({self.model!r}) needs an FD-family uplink — one "
                    "of ('fd',) + FLD_FAMILY "
                    f"{FLD_FAMILY}")
            if self.shard_devices:
                raise ValueError(
                    "mixed-architecture cohorts are not supported on the "
                    "mesh-sharded path (shard_devices=True): per-device "
                    "parameter pytrees differ across shards")
            if self.sample_ratio < 1.0:
                raise ValueError(
                    "mixed-architecture cohorts require full "
                    f"participation (sample_ratio=1.0, got "
                    f"{self.sample_ratio}): a sampled cohort would need "
                    "ragged per-architecture gathers")
        if self.n_seed < 1:
            raise ValueError(f"n_seed must be >= 1, got {self.n_seed}")
        if self.n_inverse < 1:
            raise ValueError(f"n_inverse must be >= 1, got {self.n_inverse}")
        if not 0.0 <= self.lam <= 1.0:
            raise ValueError(f"lam is a mixing ratio in [0, 1], "
                             f"got {self.lam}")

    def codec_spec(self) -> CodecSpec:
        """The resolved link codec — ``fc.channel``'s spec (the flat
        ``codec``/``quant_bits``/``dp_*`` aliases mirror its fields)."""
        return self.channel.spec()

    def cohort_size(self, pool_size: Optional[int] = None) -> int:
        """Devices training per round — ``num_devices`` unless sampling
        shrinks it.  This is the static shape every compiled round path
        sizes its device axis (and mesh, and link plan) by."""
        pool = self.num_devices if pool_size is None else pool_size
        return self.sampler.cohort_size(pool)

    def task_spec(self) -> TaskSpec:
        """The resolved task (shape / class count / payload width)."""
        return parse_task(self.task)

    def model_spec(self) -> ModelSpec:
        """The parsed model spec (``parts[0]`` is the global/server
        architecture)."""
        return parse_model(self.model)

    def server_model(self) -> str:
        """The global (server-side) architecture name."""
        return self.model_spec().parts[0]

    def model_key(self) -> str:
        """Structural model identity for program grouping: the composite
        spec name when the per-device assignment is the spec's own
        round-robin cycle, the full explicit assignment otherwise, and
        the single name for homogeneous cohorts."""
        if self.model_partition is None:
            return self.model
        parts = self.model_spec().parts
        cyc = tuple(parts[i % len(parts)] for i in range(self.num_devices))
        if tuple(self.model_partition) == cyc:
            return self.model
        return "+".join(self.model_partition)

    def arch_groups(self):
        """None for homogeneous cohorts; else the per-architecture device
        groups as ``[(name, np.int32 indices), ...]`` in first-appearance
        order over the partition (so the first group contains device 0).
        """
        if self.model_partition is None:
            return None
        part = self.model_partition
        order = list(dict.fromkeys(part))
        return [(m, np.flatnonzero(np.asarray(part) == m).astype(np.int32))
                for m in order]

    def build_models(self) -> dict:
        """Registry-built classifiers for every architecture this config
        trains (always includes the server architecture)."""
        spec_t = self.task_spec()
        names = list(self.model_partition or (self.server_model(),))
        names.append(self.server_model())
        return {m: build_model(m, spec_t.input_shape, self.num_classes)
                for m in dict.fromkeys(names)}


# ---------------------------------------------------------------------------
# Pure per-round pieces (shared by the trainer loop and the sweep engine)
# ---------------------------------------------------------------------------

def make_local_train(apply_fn, num_classes: int, local_iters: int,
                     local_batch: int):
    """Per-device local SGD (eq. 1 / 3) for one device's shard.

    ``eta``/``beta`` are *arguments* rather than baked-in constants so the
    sweep engine can vmap them over a config grid; passing the config's
    Python floats yields the same lowering as closing over them.
    ``n_loc`` bounds the batch draws — the loop path passes the static
    ``x.shape[0]``, the sweep engine a traced per-config scalar (ragged
    partitions are zero-padded to the grid maximum, and a traced bound
    equal in value to the static one draws identical indices, so pad rows
    are never sampled — same contract as the conversion's ``n_train``).
    Returns ``local_train(params, x, y, key, gout, use_kd, eta, beta,
    n_loc) -> (params, favg (C, C), cnt (C,), mean loss)``.
    """
    C = num_classes

    def local_train(params, x, y, key, gout, use_kd, eta, beta, n_loc):
        def step(carry, k):
            p, out_sum, cnt = carry
            idx = jax.random.randint(k, (local_batch,), 0, n_loc)
            xb, yb = x[idx], y[idx]

            def loss_fn(p_):
                logits = apply_fn(p_, xb)
                b = jnp.where(use_kd, beta, 0.0)
                l, _ = fd_loss(logits, yb, gout, b)
                return l, logits

            (l, logits), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
            p = jax.tree.map(lambda a, b_: a - eta * b_, p, g)
            probs = jax.nn.softmax(logits, axis=-1)
            oh = jax.nn.one_hot(yb, C)
            out_sum = out_sum + oh.T @ probs
            cnt = cnt + jnp.sum(oh, axis=0)
            return (p, out_sum, cnt), l

        with jax.named_scope("local_train"):
            init = (params, jnp.zeros((C, C)), jnp.zeros((C,)))
            (params, out_sum, cnt), losses = jax.lax.scan(
                step, init, jax.random.split(key, local_iters))
            favg = out_sum / jnp.maximum(cnt[:, None], 1.0)
            return params, favg, cnt, jnp.mean(losses)

    return local_train


@jax.tree_util.register_pytree_node_class
class _DeviceRows:
    """One model's view of shared (D, n, ...) device data: indexing it by
    sample positions gathers rows ``(dev, idx)`` straight from the shared
    array, so models vmapped over a (point, device) axis read their
    device's shard without a per-point copy of the data."""

    def __init__(self, rows, dev):
        self.rows, self.dev = rows, dev

    def __getitem__(self, idx):
        return self.rows[self.dev, idx]

    def tree_flatten(self):
        return (self.rows, self.dev), None

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(*leaves)


def make_grid_local_train(apply_fn, num_classes: int, local_iters: int,
                          local_batch: int, per_config_data: bool = False):
    """:func:`make_local_train` for a config grid: operates on (G, D, ...)
    device state with shared (D, ...) data — or, with
    ``per_config_data``, per-config (G, D, ...) data (heterogeneous
    partition grids and sampled cohorts; ragged ``n_local`` zero-padded
    to the grid maximum and masked by the per-config ``n_loc`` draw
    bound) — and per-config (G,) eta/beta/n_loc.

    The grid's G x D models are flattened into ONE vmap axis of G·D
    models, as the trainer vmaps its D devices: a vmap of a vmap gives
    every activation two batch dimensions, and the TPU lays the rank-6
    pooling and its gradient out differently from each other, with
    layout copies between them, where the rank-5 form keeps one layout.
    Shared data is not copied per point: flat model ``m`` gathers its
    batch from device ``m % D``'s rows (:class:`_DeviceRows`).  The sweep
    engine wraps this in shard_map for ``shard_devices`` grids, where
    each shard flattens its own (G_l, D_l) block."""
    base = make_local_train(apply_fn, num_classes, local_iters, local_batch)
    data_axes = 0 if per_config_data else _DeviceRows(None, 0)
    flat_train = jax.vmap(base, in_axes=(0, data_axes, data_axes, 0, 0,
                                         None, 0, 0, 0))

    def grid_local_train(params, x, y, keys, gout, use_kd, eta, beta,
                         n_loc):
        G, D = gout.shape[:2]

        def flat(a):
            return a.reshape((G * D,) + a.shape[2:])

        if per_config_data:
            x, y = flat(x), flat(y)
        else:
            dev = jnp.tile(jnp.arange(D), G)
            x, y = _DeviceRows(x, dev), _DeviceRows(y, dev)
        out = flat_train(jax.tree.map(flat, params), x, y, flat(keys),
                         flat(gout), use_kd, jnp.repeat(eta, D),
                         jnp.repeat(beta, D), jnp.repeat(n_loc, D))
        return jax.tree.map(lambda a: a.reshape((G, D) + a.shape[1:]), out)

    return grid_local_train


def weighted_avg(stacked, weights):
    """Weighted model average over the device axis (uplink-success set)."""
    with jax.named_scope("aggregate"):
        wsum = jnp.maximum(jnp.sum(weights), 1e-9)
        return jax.tree.map(
            lambda s: jnp.tensordot(weights, s, axes=1) / wsum, stacked)


def gout_update(favg, cnt, ok):
    """eq. 2: per-class output average over the successful device set."""
    with jax.named_scope("aggregate"):
        cw = ok[:, None] * cnt                  # (D, C) per-class wts
        num = jnp.einsum("dc,dcm->cm", cw, favg)
        den = jnp.sum(cw, axis=0)
        return num / jnp.maximum(den[:, None], 1.0)


def weighted_avg_psum(stacked, weights):
    """:func:`weighted_avg` for one shard of a shard_mapped device axis:
    partial tensordot over the local slice, psum over "data"."""
    with jax.named_scope("aggregate"):
        wsum = jnp.maximum(jax.lax.psum(jnp.sum(weights), "data"), 1e-9)
        part = jax.tree.map(
            lambda s: jnp.tensordot(weights, s, axes=1), stacked)
        return jax.tree.map(lambda t: jax.lax.psum(t, "data") / wsum, part)


def gout_update_psum(favg, cnt, ok):
    """:func:`gout_update` with psum collectives over the "data" axis."""
    with jax.named_scope("aggregate"):
        cw = ok[:, None] * cnt
        num = jax.lax.psum(jnp.einsum("dc,dcm->cm", cw, favg), "data")
        den = jax.lax.psum(jnp.sum(cw, axis=0), "data")
        return num / jnp.maximum(den[:, None], 1.0)


# Round-1 seed collection lives in core.seed_prep (host-side pairing and
# segment/sort cycle search, content-keyed memoization); ``collect_seeds``
# is re-exported above for the established import path.


class FederatedTrainer:
    """Runs one protocol over a simulated device population.

    model: an object with .init(key) and .apply(params, x) -> logits —
    or None to build ``fc.model`` from the registry for ``fc.task``'s
    geometry.  dev_x: (D, n_local, ...), dev_y: (D, n_local).

    A mixed cohort (``fc.model_partition`` set — FD family only) builds
    one classifier per architecture: every device trains its own
    parameter space, the eq. (2) aggregation merges the per-label output
    averages in the shared (C, C) output space, and the FLD conversion /
    parameter downlink act on the *server* architecture
    (``fc.server_model()``) alone — clients of other architectures keep
    learning through the KD tables, which is exactly the workload FL
    cannot express.
    """

    def __init__(self, model, fc: FederatedConfig,
                 ch: Optional[ChannelConfig] = None):
        assert fc.protocol in PROTOCOLS
        self.fc = fc
        self._arch_groups = fc.arch_groups()
        if self._arch_groups is not None:
            if model is not None:
                raise ValueError(
                    "mixed-architecture cohorts build their per-device "
                    "models from the registry; pass model=None")
            self.models = fc.build_models()
            model = self.models[fc.server_model()]
        elif model is None:
            model = fc.model_spec().build(fc.task_spec().input_shape,
                                          fc.num_classes)
        self.model = model
        self.ch = ch or ChannelConfig(num_devices=fc.num_devices)
        self._build()

    # ------------------------------------------------------------------
    def _build(self):
        fc = self.fc
        base = make_local_train(self.model.apply, fc.num_classes,
                                fc.local_iters, fc.local_batch)

        def local_train(params, x, y, key, gout, use_kd):
            # x is one device's (n_local, ...) shard under the vmap, so
            # the static shape is the exact batch-draw bound (the sweep
            # engine passes the same value as a traced per-config scalar)
            return base(params, x, y, key, gout, use_kd, fc.eta, fc.beta,
                        x.shape[0])

        vmapped = jax.vmap(local_train, in_axes=(0, 0, 0, 0, 0, None))

        apply_fn = self.model.apply

        def accuracy(params, x, y):
            logits = apply_fn(params, x)
            return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))

        self._accuracy = jax.jit(accuracy)

        # link-pipeline uplink codec stage (identity: bitwise pass-through
        # that consumes no PRNG — the pre-pipeline behaviour)
        self._codec = fc.codec_spec()
        self._uplink_stage = make_uplink_stage(self._codec, fc.protocol)
        self._plan_cache = {}  # LinkPlan per cohort size (see link_plan)

        # ---- mixed cohorts: one local-train / accuracy program per
        # architecture group (device indices are static, so each group's
        # vmap spans exactly its devices) ----
        self._arch_trains = None
        if self._arch_groups is not None:
            def make_pair(apply_a):
                base_a = make_local_train(apply_a, fc.num_classes,
                                          fc.local_iters, fc.local_batch)

                def lt(params, x, y, key, gout, use_kd):
                    return base_a(params, x, y, key, gout, use_kd,
                                  fc.eta, fc.beta, x.shape[0])

                def acc_a(params, x, y):
                    logits = apply_a(params, x)
                    return jnp.mean((jnp.argmax(logits, -1) == y)
                                    .astype(jnp.float32))

                return (jax.jit(jax.vmap(
                    lt, in_axes=(0, 0, 0, 0, 0, None))), jax.jit(acc_a))

            self._arch_trains, self._arch_acc = [], {}
            for arch, idx in self._arch_groups:
                lt_a, acc_a = make_pair(self.models[arch].apply)
                self._arch_trains.append((arch, np.asarray(idx), lt_a))
                self._arch_acc[arch] = acc_a

        self.mesh = None
        if not fc.shard_devices:
            self._local_train = jax.jit(vmapped)
            self._weighted_avg = jax.jit(weighted_avg)
            self._gout_update = jax.jit(gout_update)
            return

        # ---- mesh-sharded path: device axis along the "data" mesh axis,
        # reductions as psum collectives over the shards ----
        # the mesh spans the per-round cohort, not the pool: only
        # D_cohort devices ever enter the shard_mapped fns, so a sampled
        # trainer can hold a pool far larger than the chip count
        self.mesh = make_device_mesh(fc.cohort_size(),
                                     fc.mesh_shards or None)
        ps = federated_pspecs()
        dev, rep = ps["device"], ps["replicated"]
        self._local_train = jax.jit(shard_map(
            vmapped, mesh=self.mesh,
            in_specs=(dev, dev, dev, dev, dev, rep),
            out_specs=(dev, dev, dev, dev), check_vma=False))
        self._weighted_avg = jax.jit(shard_map(
            weighted_avg_psum, mesh=self.mesh, in_specs=(dev, dev),
            out_specs=rep, check_vma=False))
        self._gout_update = jax.jit(shard_map(
            gout_update_psum, mesh=self.mesh, in_specs=(dev, dev, dev),
            out_specs=rep, check_vma=False))

    # ------------------------------------------------------------------
    def collect_seeds(self, dev_x, dev_y, key):
        """See module-level :func:`collect_seeds` (this wrapper keeps the
        established trainer API)."""
        return collect_seeds(self.fc, dev_x, dev_y, key)

    # ------------------------------------------------------------------
    def init_state(self, num_devices: Optional[int] = None) -> RoundState:
        """Fresh resumable :class:`RoundState` (see :meth:`round_once`).

        ``num_devices`` sizes the device-axis state for a churned cohort
        pool larger (or smaller) than ``fc.num_devices``; the default
        reproduces ``run``'s population exactly, including its PRNG
        stream: ``key`` is the second ``split(PRNGKey(seed))`` output,
        so round p always folds to the same round key regardless of how
        many times the loop was stopped and resumed.
        """
        fc = self.fc
        D = fc.num_devices if num_devices is None else num_devices
        C = fc.num_classes
        key = jax.random.PRNGKey(fc.seed)
        kinit, key = jax.random.split(key)
        # all devices start from a common init (paper: same architecture)
        g_params = self.model.init(kinit)
        if self._arch_groups is not None:
            # per-architecture stacks: the server architecture's group
            # shares the global init; other architectures draw from a
            # deterministic fold of the same init key
            dev_params = {}
            srv = fc.server_model()
            for arch, idx in self._arch_groups:
                init_a = g_params if arch == srv else self.models[arch].init(
                    jax.random.fold_in(kinit, MODELS.index(arch) + 1))
                dev_params[arch] = jax.tree.map(
                    lambda p: jnp.broadcast_to(
                        p, (len(idx),) + p.shape).copy(), init_a)
        else:
            dev_params = jax.tree.map(
                lambda p: jnp.broadcast_to(p, (D,) + p.shape).copy(),
                g_params)
        gout = jnp.full((C, C), 1.0 / C)
        # per-device view of gout: a device only refreshes its copy when
        # its downlink succeeds (failed links keep the previous table)
        dev_gout = jnp.broadcast_to(gout, (D, C, C))
        return RoundState(round=0, key=key, g_params=g_params,
                          dev_params=dev_params, gout=gout,
                          dev_gout=dev_gout)

    def link_plan(self, g_params, n_links: Optional[int] = None) -> LinkPlan:
        """The codec-aware link plan for an ``n_links``-device cohort,
        cached per cohort size (payload bits depend only on the model
        and config, both fixed for a trainer's lifetime)."""
        fc = self.fc
        n_links = fc.num_devices if n_links is None else n_links
        plan = self._plan_cache.get(n_links)
        if plan is None:
            n_mod = sum(p.size for p in jax.tree.leaves(g_params))
            plan = LinkPlan.build(fc.protocol, self.ch, n_mod=n_mod,
                                  n_labels=fc.num_classes,
                                  sample_bits=fc.sample_bits,
                                  n_seed=fc.n_seed, codec=self._codec,
                                  n_links=n_links)
            self._plan_cache[n_links] = plan
        return plan

    def round_once(self, state, dev_x, dev_y, test_x, test_y, *,
                   plan: Optional[LinkPlan] = None, log=None,
                   _pending_link=None):
        """One federated round — ``run``'s round body as a resumable
        step.  Returns ``(new_state, record)``.

        ``state`` is :meth:`init_state`'s :class:`RoundState` (or the
        previous round's output; a legacy mapping coerces); the round
        number and every PRNG draw derive from it, so a state rebuilt
        from a checkpoint continues the exact stream an uninterrupted
        loop would have produced.  ``dev_x``/``dev_y`` are the *device
        pool*'s shards ``(D_pool, n_local, ...)`` — the device-axis
        state in ``state`` must match, which is how the serving driver
        runs churned cohorts through the same step.

        ``_pending_link`` is the double-buffering seam (private — the
        :class:`~repro.core.program.LoopRoundProgram` is the caller): a
        ``plan.dispatch`` handle for THIS round's key, collected where
        the serial path would draw.  A handle dispatched against a plan
        this round rebuilds (cohort-size change) is discarded — link
        draws are pure functions of ``(plan, key)``, so dropping one
        costs only its wasted dispatch.

        With ``fc.sample_ratio < 1`` the round trains only the seeded
        cohort of :meth:`FederatedConfig.cohort_size` devices
        (``core.sampling.SamplerConfig``): pool-axis state is gathered
        down to the cohort before local SGD, the link plan spans
        ``D_cohort`` links, and the trained cohort rows are scattered
        back into the pool afterwards — non-participants keep their
        parameters and KD tables untouched, exactly like a failed
        downlink.  At ``sample_ratio == 1`` this path is bypassed
        entirely, so full-participation histories stay bit-identical.
        """
        fc = self.fc
        proto = fc.protocol
        state = RoundState.from_mapping(state)
        dev_x = jnp.asarray(dev_x)
        dev_y = jnp.asarray(dev_y)
        D_pool = dev_x.shape[0]
        p = state.round + 1

        t0 = time.perf_counter()
        kr = jax.random.fold_in(state.key, p)
        use_kd = proto != "fl" and p > 1  # KD once G_out exists
        dev_params, g_params = state.dev_params, state.g_params
        gout, dev_gout = state.gout, state.dev_gout
        seeds = state.seeds

        # ---- client sampling: gather the round's cohort off the pool ----
        sampler = fc.sampler
        D = sampler.cohort_size(D_pool)
        cohort = None
        pool_params = pool_gout = None
        if D < D_pool:
            with jax.profiler.TraceAnnotation("cohort_io", round=p) as span:
                cohort = sampler.cohort(fc.seed, p, D_pool)
                jdx = jnp.asarray(cohort)
                pool_params, pool_gout = dev_params, dev_gout
                dev_params = jax.tree.map(lambda a: a[jdx], dev_params)
                dev_gout = dev_gout[jdx]
                dev_x, dev_y = dev_x[jdx], dev_y[jdx]
                span.set_metadata(
                    bytes=tree_nbytes(dev_params, dev_gout, dev_x, dev_y))
        # a caller-supplied plan sized for a different cohort (churn on
        # top of sampling) is rebuilt for this round's link count — and
        # any prefetched draw against the old plan with it
        if plan is None or plan.n_links != D:
            plan = self.link_plan(state.g_params, n_links=D)
            _pending_link = None

        # ---- local updates (eq. 1 / 3) ----
        with jax.profiler.TraceAnnotation("local_train", round=p):
            dkeys = jax.random.split(jax.random.fold_in(kr, 1), D)
            if self._arch_trains is None:
                dev_params, favg, cnt, mloss = self._local_train(
                    dev_params, dev_x, dev_y, dkeys, dev_gout,
                    jnp.asarray(use_kd))
            else:
                # per-architecture groups train in their own parameter
                # spaces; the (D, C, C) output tables reassemble in the
                # shared output space for the eq. (2) merge below.  Each
                # device consumes the same dkeys[d] it would draw in a
                # homogeneous cohort.
                C = fc.num_classes
                favg = jnp.zeros((D, C, C))
                cnt = jnp.zeros((D, C))
                mloss = jnp.zeros((D,))
                new_dp = {}
                for arch, idx, lt in self._arch_trains:
                    ji = jnp.asarray(idx)
                    p_a, f_a, c_a, l_a = lt(
                        dev_params[arch], dev_x[ji], dev_y[ji], dkeys[ji],
                        dev_gout[ji], jnp.asarray(use_kd))
                    new_dp[arch] = p_a
                    favg = favg.at[ji].set(f_a)
                    cnt = cnt.at[ji].set(c_a)
                    mloss = mloss.at[ji].set(l_a)
                dev_params = new_dp
            jax.block_until_ready(favg)

        # ---- seed collection (first round, FLD family) ----
        if p == 1 and proto in FLD_FAMILY:
            seeds = self.collect_seeds(dev_x, dev_y,
                                       jax.random.fold_in(kr, 2))

        # ---- link pipeline: encode -> channel -> decode ----
        # (collect the prefetched draw when the async program dispatched
        # one — same key, same plan, so bitwise the same outcome)
        with jax.profiler.TraceAnnotation("link_draw", round=p,
                                          links=plan.n_links):
            if _pending_link is not None:
                link = plan.collect(_pending_link)
            else:
                link = plan.draw(jax.random.fold_in(kr, 3),
                                 first_round=p == 1)
        up_ok = link["up_ok"]
        dn_ok = link["dn_ok"]

        # ---- aggregation + (FLD) conversion ----
        with jax.profiler.TraceAnnotation("aggregate", round=p):
            w = up_ok.astype(np.float32) * dev_x.shape[1]  # |S_d| weights
            # uplink codec: what the server receives (identity passes the
            # arrays through untouched; stochastic codecs draw from the
            # dedicated fold_in(kr, 5) stream, leaving every pre-existing
            # PRNG consumer bit-identical)
            dev_params_rx, favg_rx = self._uplink_stage(
                dev_params, favg, jax.random.fold_in(kr, 5), dev_gout,
                g_params)
            if proto == "fl":
                if up_ok.any():
                    g_params = self._weighted_avg(dev_params_rx,
                                                  jnp.asarray(w))
            elif up_ok.any():
                # eq. 2 averaged over the successful device set (psum
                # collective on the sharded path)
                gout = self._gout_update(
                    favg_rx, cnt, jnp.asarray(up_ok, jnp.float32))
        if proto in FLD_FAMILY:
            with jax.profiler.TraceAnnotation("convert", round=p):
                g_params, _ = output_to_model(
                    self.model.apply, g_params, seeds["train_x"],
                    seeds["train_y"], gout, fc.server_iters,
                    fc.server_batch, fc.eta, fc.beta,
                    jax.random.fold_in(kr, 4))

        # ---- downlink stage (gated per device by dn_ok) ----
        with jax.profiler.TraceAnnotation("downlink", round=p):
            mask = jnp.asarray(dn_ok)
            dev_gout = downlink_gout(dev_gout, gout, mask)
            if proto != "fd":
                if self._arch_groups is None:
                    dev_params = downlink_params(dev_params, g_params, mask)
                else:
                    # the converted global model lives in the server
                    # architecture's parameter space: only that group can
                    # receive it; other architectures keep training
                    # through the KD tables delivered above
                    srv = fc.server_model()
                    for arch, idx in self._arch_groups:
                        if arch == srv:
                            dev_params = dict(dev_params)
                            dev_params[srv] = downlink_params(
                                dev_params[srv], g_params,
                                mask[jnp.asarray(idx)])

        # ---- scatter the trained cohort back into the pool ----
        if cohort is not None:
            with jax.profiler.TraceAnnotation(
                    "cohort_io", round=p,
                    bytes=tree_nbytes(dev_params, dev_gout)):
                dev_params = jax.tree.map(
                    lambda pool, coh: pool.at[jdx].set(coh), pool_params,
                    dev_params)
                dev_gout = pool_gout.at[jdx].set(dev_gout)

        compute_s = time.perf_counter() - t0
        cum_time = state.cum_time_s + compute_s + link["latency_s"]

        # ---- evaluation of the round's reference device: pool device 0
        # at full participation, else the cohort's first device — it
        # just trained and received the downlink, whereas a fixed
        # device 0 sits out most rounds at small sample_ratio and its
        # stale parameters would stall the reported acc ----
        with jax.profiler.TraceAnnotation("evaluate", round=p):
            ref_dev = 0 if cohort is None else int(cohort[0])
            if self._arch_groups is None:
                ref = jax.tree.map(lambda dp: dp[ref_dev], dev_params)
                acc = float(self._accuracy(ref, test_x, test_y))
            else:
                # device 0 sits at position 0 of the first (first-
                # appearance ordered) architecture group; evaluate with
                # its own apply
                arch0 = self._arch_groups[0][0]
                ref = jax.tree.map(lambda dp: dp[0], dev_params[arch0])
                acc = float(self._arch_acc[arch0](ref, test_x, test_y))
            loss = float(mloss.mean())
        if log:
            log(f"[{proto}] round {p}: acc={acc:.3f} "
                f"loss={loss:.3f} up_ok={up_ok.sum()}/{D} "
                f"lat={link['latency_s']*1e3:.0f}ms")

        # ---- convergence (relative change < eps) ----
        # one reference for every protocol: the global soft-label table
        # for FD, the flattened global model otherwise (a Frobenius norm
        # equals the 2-norm of the ravel, so the FD numbers are the ones
        # the pre-factoring loop produced)
        with jax.profiler.TraceAnnotation("converge", round=p):
            if proto == "fd":
                flat = gout.ravel()
            else:
                flat = jnp.concatenate([jnp.ravel(x) for x in
                                        jax.tree.leaves(g_params)])
            converged_round = state.converged_round
            if state.prev is not None:
                rel = float(jnp.linalg.norm(flat - state.prev) /
                            jnp.maximum(jnp.linalg.norm(state.prev), 1e-12))
                # a total-outage round leaves the global state untouched,
                # so rel == 0 means "nothing arrived", not convergence:
                # the check only counts when at least one uplink decoded
                # (the grid path's hit mask applies the same gate)
                if rel < fc.eps and converged_round is None and \
                        bool(up_ok.any()):
                    converged_round = p

        new_state = RoundState(round=p, key=state.key, g_params=g_params,
                               dev_params=dev_params, gout=gout,
                               dev_gout=dev_gout, prev=flat,
                               converged_round=converged_round,
                               seeds=seeds, cum_time_s=cum_time)
        record = {"round": p, "acc": acc, "loss": loss,
                  "round_latency_s": link["latency_s"],
                  "compute_s": compute_s, "cum_time_s": cum_time,
                  "uplink_ok": int(up_ok.sum()),
                  "n_straggle": int(link.get("n_straggle", 0)),
                  "n_active": D,
                  "cohort": cohort,  # None: every pool device trained
                  "link": link}
        return new_state, record

    # ------------------------------------------------------------------
    def run(self, dev_x, dev_y, test_x, test_y, log=None,
            options: Optional[ProgramOptions] = None):
        """Full protocol run. Returns history dict (per-round accuracy,
        losses, latency, cumulative wall-clock convergence time).

        A thin driver over a :class:`LoopRoundProgram` — the serving
        loop (``launch.service``) drives the same program with churned
        cohorts and checkpoints between rounds.  ``options`` selects
        mesh shape / pipelining depth; the default is the strict-serial
        depth-1 program (every depth is bitwise-identical — see
        ``core.program``).
        """
        fc = self.fc
        spec = self._codec
        state = self.init_state()
        # ---- link pipeline plan: codec-aware payload bits -> slot counts
        # (sized for the per-round cohort, the devices actually on air)
        plan = self.link_plan(state["g_params"], n_links=fc.cohort_size())
        acct = (GaussianAccountant(spec.dp_sigma, spec.dp_delta,
                                   sample_ratio=fc.sample_ratio)
                if spec.name == "dp_gaussian" else None)

        history = {"acc": [], "round_latency_s": [], "compute_s": [],
                   "cum_time_s": [], "loss": [], "uplink_ok": [],
                   "converged_round": None, "protocol": fc.protocol,
                   "model": fc.model_key(), "task": fc.task,
                   "codec": spec.name,
                   "sample_ratio": fc.sample_ratio,
                   "cohort_size": fc.cohort_size(),
                   "uplink_bits_first": plan.up_bits_first,
                   "uplink_bits": plan.up_bits,
                   "downlink_bits": plan.dn_bits}
        if acct is not None:
            history["dp_epsilon"] = []

        dev_x = jnp.asarray(dev_x)
        dev_y = jnp.asarray(dev_y)
        program = LoopRoundProgram(self, options).bind(
            dev_x=dev_x, dev_y=dev_y, test_x=test_x, test_y=test_y,
            plan=plan, log=log)
        for _ in range(fc.max_rounds):
            state, rec = program.step(state)
            if acct is not None:
                # a device spends privacy budget only on rounds it
                # released a (noised) payload — i.e. its cohort rounds
                acct.step(cohort=rec["cohort"])
                history["dp_epsilon"].append(acct.epsilon())
            for k in ("acc", "loss", "round_latency_s", "compute_s",
                      "cum_time_s", "uplink_ok"):
                history[k].append(rec[k])
        history["pipeline"] = program.finalize()
        history["converged_round"] = state.converged_round

        # histories carry lightweight seed metadata, not device arrays —
        # serialized results stay small; opt back into the raw arrays
        # with FederatedConfig.keep_seed_arrays
        history["seeds"] = summarize_seeds(state["seeds"])
        if acct is not None:
            history["dp"] = acct.ledger()
        if fc.keep_seed_arrays:
            history["seed_arrays"] = state["seeds"]
        history["final_acc"] = history["acc"][-1]
        # per-device KD tables (tests inspect)
        self.last_dev_gout = state["dev_gout"]
        return history


# ---------------------------------------------------------------------------
# Grid-batched round step (the protocol-sweep engine's compiled core)
# ---------------------------------------------------------------------------

def make_grid_round_step(model_apply, *, protocol: str, num_devices: int,
                         num_classes: int, local_iters: int,
                         local_batch: int, server_batch: int,
                         t_max_slots: int, tau_s: float,
                         dev_x, dev_y, test_x, test_y, consts: dict,
                         per_config_data: bool = False,
                         local_train_fn: Optional[Callable] = None,
                         weighted_avg_fn: Optional[Callable] = None,
                         gout_update_fn: Optional[Callable] = None,
                         grid_shard: Optional[Callable] = None,
                         codec: str = "identity",
                         cohort_size: Optional[int] = None,
                         arch_groups: Optional[list] = None):
    """Pure per-round protocol step batched over a leading config-grid
    axis — ``FederatedTrainer.run``'s round body with every host decision
    (success gating, convergence bookkeeping) expressed as masked lax ops,
    so ``jax.lax.scan`` over rounds compiles a whole G-point grid into
    one program.

    ``consts`` holds the per-config traced constants, every leaf with a
    leading grid axis G:

    ======================  ======================================
    ``key``       (G, 2)    per-config round key — the *second* output of
                            ``split(PRNGKey(seed))`` exactly as in ``run``
    ``eta, beta`` (G,)      SGD step / KD weight (local SGD *and* the
                            eq. 5 conversion, as in the loop path)
    ``s_iters``   (G,)      conversion iterations (masked to the grid max)
    ``eps``       (G,)      convergence threshold
    ``n_local``   (G,)      per-config |S_d| — the local batch-draw bound
                            and the aggregation weight (heterogeneous
                            partition grids pad ragged partitions to the
                            grid maximum; the traced bound masks the pad)
    ``n_train``   (G,)      live prefix of the padded seed sets
    ``seeds_x``   (G, N, ...), ``seeds_y`` (G, N[, C])  padded seed sets
    ``p_up, p_dn`` (G,)     per-slot link success probabilities
    ======================  ======================================

    ``dev_x``/``dev_y`` are shared (D, n, ...) data by default; with
    ``per_config_data`` they carry a leading grid axis (G, D, n, ...) —
    one (padded) partition per config.

    The scan inputs ``xs`` per round: ``p`` (scalar, 1-based round),
    ``up_slots``/``dn_slots`` (G,) decode-slot requirements, and
    ``conv_keys`` (G, K_max, 2) host-precomputed conversion step keys
    (``jax.random.split`` is not prefix-stable, so ragged per-config
    ``s_iters`` can't split in-graph and stay equal to the loop path).

    State: a grid-layout :class:`RoundState` carry — ``dev_params``
    (G, D, ...), ``g_params`` (G, ...), ``gout`` (G, C, C), ``dev_gout``
    (G, D, C, C), ``prev`` (G, P) flattened convergence reference,
    ``converged_round`` (G,) int32 (0 = not yet); the loop path's host
    fields (``round``/``key``/``seeds``/``cum_time_s``) ride as None so
    the scan carry structure is stable.

    ``local_train_fn``/``weighted_avg_fn``/``gout_update_fn`` default to
    the vmapped single-chip forms; the sweep engine substitutes
    shard_mapped variants (device axis on the "data" mesh) for
    ``shard_devices`` grids.  ``grid_shard``, when given, wraps the
    G-vmapped eq. 5 conversion and evaluation (every argument and output
    G-leading) so that each shard of a ``"grid"`` mesh axis runs them for
    its own points only; left to the compiler, they are all-gathered and
    run at the full grid width on every chip.

    ``codec`` is the link codec *family* of this program (a structural
    axis: the sweep engine compiles one program per (protocol, codec)
    group).  Non-identity codecs read their numeric parameters from
    ``consts`` — ``q_levels``/``dp_sigma``/``dp_clip``, each (G,) — so
    quantization bit widths and DP noise sweep inside one program; the
    identity codec touches neither consts nor PRNG, keeping the compiled
    graph exactly the pre-pipeline one.

    ``cohort_size`` < ``num_devices`` turns on per-round client sampling
    (a structural axis like the codec family: the engine groups points by
    cohort size).  ``xs`` then carries ``cohort`` (G, D_cohort) int32 —
    host-precomputed sorted ``SamplerConfig.cohort`` draws — and the step
    gathers pool-axis state/data down to the cohort, trains ``D_cohort``
    devices through the identical round body (local SGD, ``D_cohort``
    channel links, codec, aggregation, downlink), and scatters the
    cohort rows back into the (G, D_pool, ...) carry.  When
    ``cohort_size`` is None or covers the pool, no gather/scatter (or
    ``cohort`` input) exists in the graph at all, so full-participation
    programs stay graph-identical to the unsampled step.

    ``arch_groups`` turns on mixed-architecture cohorts (FD family,
    full participation): a list of ``(name, device_indices, apply_fn)``
    triples in first-appearance order over the device partition (so the
    first group holds device 0, and — by the round-robin assignment
    contract — the *server* architecture whose apply is
    ``model_apply``).  ``state["dev_params"]`` becomes a dict of
    per-architecture (G, D_a, ...) stacks; each group runs its own grid
    local-train, the (G, D, C, C) output tables reassemble for the
    eq. (2) merge, and the FLD parameter downlink reaches only the
    server architecture's group.  Homogeneous programs pass None and
    keep the exact pre-refactor graph.
    """
    proto = canonical_protocol(protocol)
    D, C = num_devices, num_classes
    Dc = D if cohort_size is None else min(int(cohort_size), D)
    sampled = Dc < D
    codec_spec = parse_codec(codec)
    if arch_groups is not None:
        if sampled:
            raise ValueError("mixed-architecture grid programs require "
                             "full participation")
        if proto == "fl":
            raise ValueError("protocol 'fl' cannot mix architectures")
        arch_lt = [(a, np.asarray(idx, np.int32),
                    make_grid_local_train(fn, C, local_iters, local_batch,
                                          per_config_data))
                   for a, idx, fn in arch_groups]

    if local_train_fn is None and arch_groups is None:
        # a sampled gather of shared (D, n, ...) data yields per-config
        # (G, Dc, n, ...) batches, so the grid local-train needs the
        # per-config in_axes layout even on shared-data grids
        local_train_fn = make_grid_local_train(model_apply, C, local_iters,
                                               local_batch,
                                               per_config_data or sampled)
    if weighted_avg_fn is None:
        weighted_avg_fn = jax.vmap(weighted_avg)
    if gout_update_fn is None:
        gout_update_fn = jax.vmap(gout_update)

    def conv_one(params, sx, sy, gout, keys, iters, n_train, eta, beta):
        return output_to_model_steps(model_apply, params, sx, sy, gout,
                                     keys, iters, n_train, server_batch,
                                     eta, beta)

    conv_fn = jax.vmap(conv_one)

    # the reference device for evaluation is device 0 — in a mixed
    # cohort that is the first group's architecture, not necessarily the
    # server's
    ref_apply = arch_groups[0][2] if arch_groups is not None else model_apply

    def acc_one(params):
        logits = ref_apply(params, test_x)
        return jnp.mean((jnp.argmax(logits, -1) == test_y)
                        .astype(jnp.float32))

    acc_fn = jax.vmap(acc_one)
    if grid_shard is not None:
        conv_fn, acc_fn = grid_shard(conv_fn), grid_shard(acc_fn)

    def flatten_grid(tree):
        return jnp.concatenate(
            [x.reshape(x.shape[0], -1) for x in jax.tree.leaves(tree)],
            axis=1)

    channel_fn = jax.vmap(channel_stage,
                          in_axes=(0, 0, 0, 0, 0, None, None, None))
    codec_fn = jax.vmap(
        lambda dp, fa, k, dg, gp, lv, sg, cl: uplink_stage(
            codec_spec, proto, dp, fa, k, dg, gp, lv, sg, cl))

    def round_step(state, xs):
        p = xs["p"]
        kr = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
            consts["key"], p)
        use_kd = (p > 1) if proto != "fl" else jnp.asarray(False)

        # ---- client sampling: gather the round's cohort (G, Dc, ...)
        # off the (G, D, ...) pool carry ----
        pool_params, pool_gout = state.dev_params, state.dev_gout
        if sampled:
            with jax.named_scope("cohort_io"):
                chrt = xs["cohort"]                      # (G, Dc) int32
                take = jax.vmap(lambda a, i: a[i])
                dev_params = jax.tree.map(lambda a: take(a, chrt),
                                          pool_params)
                dev_gout = take(pool_gout, chrt)
                if per_config_data:
                    dx, dy = take(dev_x, chrt), take(dev_y, chrt)
                else:
                    dx, dy = dev_x[chrt], dev_y[chrt]    # (G, Dc, n, ...)
        else:
            dev_params, dev_gout = pool_params, pool_gout
            dx, dy = dev_x, dev_y

        # ---- local updates (eq. 1 / 3) ----
        dkeys = jax.vmap(
            lambda k: jax.random.split(jax.random.fold_in(k, 1), Dc))(kr)
        if arch_groups is None:
            dev_params, favg, cnt, mloss = local_train_fn(
                dev_params, dx, dy, dkeys, dev_gout,
                use_kd, consts["eta"], consts["beta"], consts["n_local"])
        else:
            # per-architecture groups train their own (G, D_a, ...)
            # stacks; outputs reassemble on the full device axis so the
            # eq. (2) merge below sees the whole cohort.  dkeys spans all
            # D devices, so each device draws the stream a homogeneous
            # cohort would give it.
            G = consts["key"].shape[0]
            favg = jnp.zeros((G, D, C, C))
            cnt = jnp.zeros((G, D, C))
            mloss = jnp.zeros((G, D))
            new_dp = {}
            for arch, idx, lt in arch_lt:
                ji = jnp.asarray(idx)
                dx_a = dx[:, ji] if per_config_data else dx[ji]
                dy_a = dy[:, ji] if per_config_data else dy[ji]
                p_a, f_a, c_a, l_a = lt(
                    dev_params[arch], dx_a, dy_a, dkeys[:, ji],
                    dev_gout[:, ji], use_kd, consts["eta"],
                    consts["beta"], consts["n_local"])
                new_dp[arch] = p_a
                favg = favg.at[:, ji].set(f_a)
                cnt = cnt.at[:, ji].set(c_a)
                mloss = mloss.at[:, ji].set(l_a)
            dev_params = new_dp

        # ---- channel (batched SNR/outage draws over the grid) ----
        ck = jax.vmap(lambda k: jax.random.fold_in(k, 3))(kr)
        link = channel_fn(ck, consts["p_up"], xs["up_slots"],
                          consts["p_dn"], xs["dn_slots"], Dc, t_max_slots,
                          tau_s)
        up_ok = link["up_ok"]                        # (G, Dc)
        dn_ok = link["dn_ok"]                        # (G, Dc)
        w = up_ok.astype(jnp.float32) * \
            consts["n_local"].astype(jnp.float32)[:, None]
        any_up = jnp.any(up_ok, axis=1)              # (G,)

        # ---- uplink codec stage (same stage function as the loop path,
        # vmapped over the grid; identity skips it entirely so identity
        # programs stay graph-identical to the pre-pipeline step) ----
        if codec_spec.name == "identity":
            dev_params_rx, favg_rx = dev_params, favg
        else:
            kc = jax.vmap(lambda k: jax.random.fold_in(k, 5))(kr)
            dev_params_rx, favg_rx = codec_fn(
                dev_params, favg, kc, dev_gout,
                state.g_params, consts["q_levels"],
                consts["dp_sigma"], consts["dp_clip"])

        # ---- aggregation + (FLD) conversion, success-gated by where ----
        g_params, gout = state.g_params, state.gout
        if proto == "fl":
            new_g = weighted_avg_fn(dev_params_rx, w)
            g_params = jax.tree.map(
                lambda n_, o: jnp.where(
                    any_up.reshape((-1,) + (1,) * (o.ndim - 1)), n_, o),
                new_g, g_params)
        else:
            new_gout = gout_update_fn(favg_rx, cnt,
                                      up_ok.astype(jnp.float32))
            gout = jnp.where(any_up[:, None, None], new_gout, gout)
            if proto != "fd":
                g_params, _ = conv_fn(
                    g_params, consts["seeds_x"], consts["seeds_y"], gout,
                    xs["conv_keys"], consts["s_iters"], consts["n_train"],
                    consts["eta"], consts["beta"])

        # ---- downlink stage (gated per device by dn_ok) ----
        dev_gout = downlink_gout(dev_gout, gout, dn_ok)
        if proto != "fd":
            if arch_groups is None:
                dev_params = downlink_params(dev_params, g_params, dn_ok)
            else:
                # the converted global model is server-architecture
                # parameters: only that group (the first, by the
                # round-robin contract) receives it
                a0, i0 = arch_lt[0][0], jnp.asarray(arch_lt[0][1])
                dev_params = dict(dev_params)
                dev_params[a0] = downlink_params(
                    dev_params[a0], g_params, dn_ok[:, i0])

        # ---- scatter the trained cohort back into the pool carry ----
        if sampled:
            with jax.named_scope("cohort_io"):
                scatter = jax.vmap(
                    lambda pool, i, coh: pool.at[i].set(coh))
                dev_params = jax.tree.map(
                    lambda pool, coh: scatter(pool, chrt, coh),
                    pool_params, dev_params)
                dev_gout = scatter(pool_gout, chrt, dev_gout)

        # ---- evaluation of the round's reference device: pool device 0
        # at full participation, else each config's first cohort device
        # (mirrors the loop path — a fixed device 0 goes stale under
        # sampling) ----
        if sampled:
            ref = jax.tree.map(
                lambda dp: jax.vmap(lambda a, i: a[i])(dp, chrt[:, 0]),
                dev_params)
        elif arch_groups is not None:
            # device 0 = position 0 of the first architecture group
            ref = jax.tree.map(lambda dp: dp[:, 0],
                               dev_params[arch_lt[0][0]])
        else:
            ref = jax.tree.map(lambda dp: dp[:, 0], dev_params)
        acc = acc_fn(ref)

        # ---- convergence (relative change < eps), first hit recorded ----
        if proto == "fd":
            flat = gout.reshape(gout.shape[0], -1)
        else:
            flat = flatten_grid(g_params)
        rel = jax.vmap(
            lambda a, b: jnp.linalg.norm(a - b) /
            jnp.maximum(jnp.linalg.norm(b), 1e-12))(flat, state.prev)
        # any_up mirrors the loop path's total-outage gate: an untouched
        # global state (rel == 0) on a round where nothing decoded is
        # not convergence
        hit = (p >= 2) & (rel < consts["eps"]) & any_up & \
            (state.converged_round == 0)
        converged = jnp.where(hit, p, state.converged_round)

        out = {"acc": acc, "loss": jnp.mean(mloss, axis=1),
               "latency_s": link["latency_s"],
               "up_ok": jnp.sum(up_ok, axis=1).astype(jnp.int32)}
        new_state = state.replace(
            dev_params=dev_params, g_params=g_params, gout=gout,
            dev_gout=dev_gout, prev=flat, converged_round=converged)
        return new_state, out

    return round_step
