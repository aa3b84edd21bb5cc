"""sweep: ``SweepRunner`` over a hyperparameter grid.

Set-up makes the population on the device, builds the runner (one
compiled scan per protocol group, round-1 seed prep on the host) and
calls ``run()`` once, which compiles.  The window repeats ``run()``;
each call replays every grid point's rounds from the same seeded start,
so every call must return what the first did, bit for bit.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from chipbench import checks, flops, traffic as gen
from chipbench.fed import (channel_config, control_seeds, federated_config,
                           kernel_shape, seed_record)
from chipbench.reference import Reference


class Cell:
    def __init__(self, ctx):
        from repro.sweep import SweepRunner, make_grid

        cfg, tr = ctx.config, ctx.traffic
        self.ctx = ctx
        self.data = jax.block_until_ready(gen.make(ctx.data_key, cfg, tr))
        ctx.mark("population made")
        fc = dataclasses.replace(federated_config(cfg, tr, ctx.seed),
                                 max_rounds=int(tr["rounds_per_run"]))
        self.axes = {k: tuple(v) for k, v in tr["grid"].items()}
        grid = make_grid(fc, channel_config(cfg, tr), **self.axes)
        self.points = [{"eta": f.eta, "p_up_dbm": c.p_up_dbm}
                       for f, c in grid.points]
        self.runner = SweepRunner(None, grid, *self.data)
        ctx.mark("runner built (seed prep)")
        first = self.runner.run()
        ctx.mark("first run")
        self.first = first
        self.seeds = [seed_record(s) if s is not None else None
                      for s in (self.runner.seed_sets or [None] * grid.size)]
        n = int(ctx.workload["check_rounds"])
        self.records = [{"loss": first.loss[g, :n].tolist(),
                         "acc": first.acc[g, :n].tolist(),
                         "uplinks": first.up_ok[g, :n].tolist()}
                        for g in range(grid.size)]
        for rec, s in zip(self.records, self.seeds):
            if s is not None:
                rec["seeds"] = s
        self.rounds_per_run = grid.size * first.rounds
        self.failed = 0
        self.flops_per_round = flops.round_flops(
            cfg, trained_devices=fc.cohort_size(),
            convert=fc.protocol != "fd")
        self.programs = {}
        self.kernel = kernel_shape(cfg, grid.size * fc.cohort_size() *
                                   int(cfg["local_batch"]))

    def step(self) -> int:
        res = self.runner.run()
        if not (np.array_equal(res.loss, self.first.loss) and
                np.array_equal(res.acc, self.first.acc)):
            self.failed += self.rounds_per_run
        return self.rounds_per_run

    def sync(self) -> None:
        """``run()`` returns host arrays: the device work is done."""

    def release(self) -> None:
        self.runner = None

    def reference_records(self, dtype=None, fault=None) -> list:
        import jax.numpy as jnp

        ref = Reference(self.ctx.config, self.ctx.traffic,
                        dtype or jnp.float32, fault)
        n = int(self.ctx.workload["check_rounds"])
        fd = self.ctx.traffic["protocol"] == "fd"
        if not fd and None in self.seeds:
            raise checks.MissingOutput("the program kept no round-1 seeds")
        uploads = None if fd else ref.uploads(self.ctx.seed, *self.data[:2])
        records = []
        for pt, s in zip(self.points, self.seeds):
            rec = ref.replay(self.ctx.seed, self.data, n, s, **pt)
            if uploads is not None:
                rec["uploads"] = uploads
                if dtype is not None:
                    rec["seeds"] = control_seeds(s, uploads, dtype)
            records.append(rec)
        return records

    def program_records(self) -> list:
        return self.records


def setup(ctx):
    return Cell(ctx)
