"""service_loop: ``FederatedService.step`` over a device pool.

Set-up makes the pool on the device, clears the checkpoint directory
(``out/<cell>/ckpt``, git-ignored), builds the service with a
checkpoint period longer than any window, steps it through the
workload's ``setup_rounds`` and writes one checkpoint, so the window's
first save finds its directory and programs in place.  In the window
the driver saves every ``ckpt_every`` rounds itself and times each save
on the host clock.
"""
from __future__ import annotations

import shutil
import time

import jax

from chipbench import flops, traffic as gen
from chipbench.fed import (LoopCell, channel_config, federated_config,
                           kernel_shape)

NEVER = 10 ** 9  # the service's own checkpoint period: never in a window


class Cell(LoopCell):
    def __init__(self, ctx):
        from repro.core.program import ProgramOptions
        from repro.core.sampling import ChurnConfig
        from repro.launch.service import FederatedService

        cfg, tr = ctx.config, ctx.traffic
        super().__init__(ctx, gen.make(ctx.data_key, cfg, tr))
        jax.block_until_ready(self.data)
        ctx.mark("population made")
        ckpt = ctx.out / "ckpt"
        shutil.rmtree(ckpt, ignore_errors=True)
        fc = federated_config(cfg, tr, ctx.seed)
        if fc.cohort_size() != int(tr["cohort"]):
            raise ValueError(f"sample_ratio {fc.sample_ratio} gives "
                             f"{fc.cohort_size()} devices, not "
                             f"{tr['cohort']}")
        self.svc = FederatedService(
            None, fc, channel_config(cfg, tr),
            churn=ChurnConfig(p_active=float(tr["p_active"])),
            ckpt_dir=str(ckpt), ckpt_every=NEVER, keep=1,
            options=ProgramOptions(pipeline_depth=int(tr["pipeline_depth"])))
        self.svc.bind_data(*self.data)
        g0 = self.svc.state.g_params
        for _ in range(int(ctx.workload["setup_rounds"])):
            rec = self.svc.step()
            self.note(rec, self.svc.state, g0, fc.protocol)
            ctx.mark(f"round {rec['round']}")
        self.svc.save_checkpoint()
        ctx.mark("first checkpoint")
        self.every = int(tr["ckpt_every"])
        self.flops_per_round = flops.round_flops(
            cfg, trained_devices=fc.cohort_size(),
            convert=fc.protocol != "fd")
        self.programs = {"local_train": "local_train",
                         "convert": "output_to_model"}
        self.kernel = kernel_shape(cfg, fc.cohort_size() *
                                   int(cfg["local_batch"]))

    def step(self) -> int:
        rec = self.svc.step()
        if rec["round"] % self.every == 0:
            t0 = time.perf_counter()
            self.svc.save_checkpoint()
            self.saves_ms.append((time.perf_counter() - t0) * 1e3)
        return self.count(rec)

    def sync(self) -> None:
        jax.block_until_ready(self.svc.state)

    def release(self) -> None:
        self.svc = None


def setup(ctx):
    return Cell(ctx)
