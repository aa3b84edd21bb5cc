"""The program's configuration objects, built from a cell's files.

This module and the drivers are the only parts of the benchmark that
import the program under test (``repro``)."""
from __future__ import annotations

import numpy as np

from chipbench import checks
from chipbench.reference import Reference, leaf_norms


def federated_config(config: dict, traffic: dict, seed: int, **extra):
    from repro.core.protocols import FederatedConfig
    from repro.core.sampling import SamplerConfig

    keys = ("local_iters", "local_batch", "server_iters", "server_batch",
            "eta", "beta", "eps", "lam", "n_seed", "n_inverse")
    return FederatedConfig(
        protocol=traffic["protocol"], num_devices=int(config["num_devices"]),
        num_classes=int(config["num_classes"]), model=config["model"],
        task=config["task"], seed=seed,
        sampler=SamplerConfig(sample_ratio=float(traffic["sample_ratio"])),
        **{k: config[k] for k in keys}, **extra)


def channel_config(config: dict, traffic: dict):
    from repro.channel import ChannelConfig

    deadline = traffic.get("deadline_s")
    return ChannelConfig(
        **config["channel"],
        compute_mean_s=float(traffic.get("compute_mean_s") or 0.0),
        deadline_s=float("inf") if deadline is None else float(deadline))


def state_norms(state, g0, protocol: str) -> dict:
    """Norm of every leaf's change since the initial global model: of
    the global model (FLD family) or of the whole device pool (FD)."""
    import jax

    if protocol == "fd":
        diff = jax.tree.map(lambda a, b: a - b[None], state.dev_params, g0)
    else:
        diff = jax.tree.map(lambda a, b: a - b, state.g_params, g0)
    return leaf_norms(diff)


class LoopCell:
    """What the loop and service drivers share: the program's readings
    of the set-up rounds, and the reference that replays them."""

    def __init__(self, ctx, data):
        self.ctx = ctx
        self.data = data
        self.record = {"loss": [], "acc": [], "uplinks": []}
        self.seeds = None
        self.failed = 0
        self.saves_ms = []

    def note(self, rec, state, g0, protocol: str) -> None:
        """Append one set-up round's readings, up to the workload's
        ``check_rounds`` (all set-up rounds where it names none)."""
        if len(self.record["loss"]) >= int(self.ctx.workload.get(
                "check_rounds", self.ctx.workload["setup_rounds"])):
            return
        self.record["loss"].append(float(rec["loss"]))
        self.record["acc"].append(float(rec["acc"]))
        self.record["uplinks"].append(int(rec["uplink_ok"]))
        norms = state_norms(state, g0, protocol)
        if len(self.record["loss"]) == 1:
            self.record["update_norms"] = norms
        self.record["change_norms"] = norms
        self.record["gout"] = np.asarray(state.gout, np.float64)
        if state.seeds is not None and self.seeds is None:
            self.seeds = seed_record(state.seeds)

    def count(self, rec) -> int:
        if not (np.isfinite(rec["loss"]) and np.isfinite(rec["acc"])):
            self.failed += 1
        return 1

    def reference_records(self, dtype=None, fault=None) -> list:
        import jax.numpy as jnp

        if self.ctx.traffic["protocol"] != "fd" and self.seeds is None:
            raise checks.MissingOutput("the program kept no round-1 seeds")
        ref = Reference(self.ctx.config, self.ctx.traffic,
                        dtype or jnp.float32, fault)
        rec = ref.replay(self.ctx.seed, self.data, len(self.record["loss"]),
                         self.seeds)
        if self.seeds is not None:
            rec["uploads"] = ref.uploads(self.ctx.seed, *self.data[:2])
            if dtype is not None:
                rec["seeds"] = control_seeds(self.seeds, rec["uploads"],
                                             dtype)
        return [rec]

    def program_records(self) -> list:
        if self.seeds is None:
            return [self.record]
        return [{**self.record, "seeds": self.seeds}]


def control_seeds(seeds: dict, uploads: dict, dtype) -> dict:
    """The control's seed set: the uploads as the reference rebuilt them
    in ``dtype``, and the program's seed samples rounded to ``dtype``."""
    import jax.numpy as jnp

    x = jnp.asarray(seeds["train_x"]).astype(dtype).astype(jnp.float32)
    return {**seeds, "uploaded": uploads["x"], "train_x": np.asarray(x)}


def seed_record(seeds: dict) -> dict:
    """The program's round-1 seed set as the check reads it: the
    uploads, the server's training set and its group lengths in order
    (symmetric pairs, then label cycles of 3, 4, ...)."""
    groups = [int(n) for n, count in (seeds.get("cycle_hist") or {}).items()
              for _ in range(int(count))]
    return {"uploaded": np.asarray(seeds["uploaded"]),
            "train_x": seeds["train_x"], "train_y": seeds["train_y"],
            "groups": sorted(groups)}


def kernel_shape(config: dict, rows_per_call: int) -> dict:
    return {"rows": rows_per_call, "classes": int(config["num_classes"])}
