"""trainer_loop: ``FederatedTrainer``'s host round loop.

Set-up makes the population on the device, builds the trainer and its
``LoopRoundProgram`` (the program ``FederatedTrainer.run`` drives), and
steps it through the workload's ``setup_rounds`` (round 1 collects the
Mix2FLD seeds; rounds 2 and 3 lower the convergence check's programs).
The window steps the same program on, one round per step.
"""
from __future__ import annotations

import jax

from chipbench import flops, traffic as gen
from chipbench.fed import (LoopCell, channel_config, federated_config,
                           kernel_shape)


class Cell(LoopCell):
    def __init__(self, ctx):
        from repro.core.program import LoopRoundProgram, ProgramOptions
        from repro.core.protocols import FederatedTrainer

        cfg, tr = ctx.config, ctx.traffic
        super().__init__(ctx, gen.make(ctx.data_key, cfg, tr))
        jax.block_until_ready(self.data)
        ctx.mark("population made")
        dev_x, dev_y, test_x, test_y = self.data
        fc = federated_config(cfg, tr, ctx.seed)
        trainer = FederatedTrainer(None, fc, channel_config(cfg, tr))
        state = trainer.init_state()
        g0 = state.g_params
        plan = trainer.link_plan(g0, n_links=fc.cohort_size())
        opts = ProgramOptions(pipeline_depth=int(tr["pipeline_depth"]))
        self.program = LoopRoundProgram(trainer, opts).bind(
            dev_x=dev_x, dev_y=dev_y, test_x=test_x, test_y=test_y,
            plan=plan)
        for _ in range(int(ctx.workload["setup_rounds"])):
            state, rec = self.program.step(state)
            self.note(rec, state, g0, fc.protocol)
            ctx.mark(f"round {rec['round']}")
        self.state = jax.block_until_ready(state)
        self.flops_per_round = flops.round_flops(
            cfg, trained_devices=fc.cohort_size(),
            convert=fc.protocol != "fd")
        self.programs = {"local_train": "local_train",
                         "convert": "output_to_model"}
        self.kernel = kernel_shape(cfg, fc.cohort_size() *
                                   int(cfg["local_batch"]))

    def step(self) -> int:
        self.state, rec = self.program.step(self.state)
        return self.count(rec)

    def sync(self) -> None:
        jax.block_until_ready(self.state)

    def release(self) -> None:
        self.program = self.state = None


def setup(ctx):
    return Cell(ctx)
