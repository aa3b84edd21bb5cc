"""RoundProgram: the one execution API every round path builds.

Four call surfaces used to drive rounds with four divergent signatures —
``FederatedTrainer.run``'s inline loop, ``FederatedTrainer.round_once``
(the serving driver's step), the sweep engine's jitted scan-over-rounds
(``_ProtocolProgram``), and ``launch.service``.  This module fronts them
with one contract:

    build a program  ->  ``step(state, xs)``  ->  ``finalize()``

* :class:`ProgramOptions` — the execution knobs that used to be
  per-caller plumbing: the 2-D ``(grid, device)`` mesh shape and the
  channel/compute pipelining depth.
* :class:`LoopRoundProgram` — the host round loop (trainer + service).
  At ``pipeline_depth > 1`` it double-buffers: round ``p``'s channel,
  outage and straggler draws are *dispatched* (``LinkPlan.dispatch``)
  up to ``depth - 1`` rounds before round ``p`` runs, so the link sim
  executes while earlier rounds' local SGD holds the chip.  Legal
  because a link outcome is a pure function of ``(plan, key)`` and the
  key of round ``q`` is ``fold_in(fold_in(run_key, q), 3)`` — known
  from round 1 — never of training state.  ``depth = 1`` is the
  strict-serial path, the bitwise oracle the ``serial_max_dev == 0``
  benchmark gate compares against.
* :class:`GridRoundProgram` — the compiled sweep program: a jitted
  ``lax.scan`` of ``make_grid_round_step``'s round step over the xs the
  engine precomputes, carrying a grid-layout :class:`RoundState`.  Here
  the channel sim is *already* inside the one fused program (the scan
  body interleaves it at the XLA level), so ``pipeline_depth`` does not
  apply; the mesh option does — the engine lays grid points along the
  ``"grid"`` axis of ``launch.mesh.make_grid_mesh``'s 2-D mesh.

The state threaded through every program is the frozen
:class:`~repro.core.state.RoundState` pytree.

Every round path names its work for JAX's profiler (``docs/tracing.md``):
host spans (:data:`SPANS`, ``jax.profiler.TraceAnnotation``) that cost
about a microsecond each when no profiler session is running, and device
name scopes (:data:`SCOPES`, ``jax.named_scope``) that are metadata only.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax

from .state import RoundState

#: Host spans of the round paths.  Each span of a round carries
#: ``round=p``; within a round they are disjoint, except that
#: ``checkpoint.d2h`` nests in ``checkpoint``.  No span covers a whole
#: round.
SPANS = ("cohort_io", "local_train", "link_draw", "aggregate", "convert",
         "downlink", "evaluate", "converge", "checkpoint", "checkpoint.d2h",
         "sweep_group")

#: Device name scopes inside the traced round pieces; the compiled sweep
#: scan carries the same names as the host loop's programs.
SCOPES = ("local_train", "link_draw", "aggregate", "convert", "cohort_io")


def tree_nbytes(*trees) -> int:
    """Bytes of every array in ``trees``, from shapes alone (a span's
    ``bytes`` argument; nothing waits on the device)."""
    return sum(x.nbytes for x in jax.tree.leaves(trees))


@dataclasses.dataclass(frozen=True)
class ProgramOptions:
    """Execution options shared by every round program.

    ``mesh_shape`` — ``(grid_shards, device_shards)`` for the 2-D pod
    mesh (grid programs) or ``(1, device_shards)``-equivalent 1-D
    sharding (loop programs ignore the grid entry); ``None`` lets
    ``launch.mesh`` auto-shape from the available chips — or the
    roofline model pick it (``roofline.analysis.recommend_execution``).

    ``pipeline_depth`` — how many rounds of link draws may be in flight
    at once.  1 = strict serial (dispatch and collect back-to-back);
    2 = classic double buffering (round p+1's draw on the wire during
    round p's SGD).  Depth only changes *when* draws are dispatched,
    never what they return, so every depth is bitwise-identical.
    """
    mesh_shape: Optional[tuple] = None
    pipeline_depth: int = 1

    def __post_init__(self):
        if self.pipeline_depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1, "
                             f"got {self.pipeline_depth}")
        if self.mesh_shape is not None:
            shape = tuple(int(s) for s in self.mesh_shape)
            if len(shape) != 2 or any(s < 1 for s in shape):
                raise ValueError(f"mesh_shape must be two positive ints "
                                 f"(grid, device), got {self.mesh_shape}")
            object.__setattr__(self, "mesh_shape", shape)


class LoopRoundProgram:
    """The host round loop behind one ``step(state, xs)`` face.

    ``xs`` is the loop path's per-round input bundle — a dict with
    ``dev_x``/``dev_y``/``test_x``/``test_y`` and optionally ``plan``
    and ``log``; data that never changes can be bound once with
    :meth:`bind` and omitted from every step.

    Double buffering (``options.pipeline_depth = d``): entering round
    ``p``, the program tops up its in-flight window so the draws of
    rounds ``p .. p + d - 1`` are dispatched, then hands round ``p``'s
    (by now usually complete) handle to ``round_once`` for collection.
    The window is keyed by round number and invalidated whenever the
    round's plan differs from the dispatched one (a cohort-size change
    under churn) — a stale handle is simply dropped, because draws are
    pure and re-drawing is cheap.
    """

    def __init__(self, trainer, options: Optional[ProgramOptions] = None):
        self.trainer = trainer
        self.options = options or ProgramOptions()
        self._bound: dict = {}
        self._pending: dict = {}   # round -> (plan, dispatch handle)
        self.dispatched = 0        # prefetches issued (bench inspects)
        self.collected = 0         # prefetches actually consumed

    def bind(self, **xs) -> "LoopRoundProgram":
        """Fix step inputs (``dev_x=..., test_x=...``) for every round."""
        self._bound.update(xs)
        return self

    # -- double-buffer window -----------------------------------------
    def _round_key(self, state: RoundState, q: int):
        return jax.random.fold_in(jax.random.fold_in(state.key, q), 3)

    def _top_up(self, state: RoundState, plan) -> None:
        """Dispatch link draws for every round in the look-ahead window
        that has none in flight yet."""
        p = state.round + 1
        for q in range(p, p + self.options.pipeline_depth):
            if q not in self._pending:
                with jax.profiler.TraceAnnotation("link_draw", round=q,
                                                  links=plan.n_links):
                    self._pending[q] = (plan, plan.dispatch(
                        self._round_key(state, q), first_round=q == 1))
                self.dispatched += 1
        # drop handles for rounds the loop has already passed (restores)
        for q in list(self._pending):
            if q < p:
                del self._pending[q]

    def step(self, state, xs: Optional[dict] = None):
        """One round: returns ``(new_state, record)`` exactly like
        ``round_once`` — because it IS ``round_once``, plus the
        dispatch window management around it."""
        xs = {**self._bound, **(xs or {})}
        state = RoundState.from_mapping(state)
        plan = xs.get("plan")
        if plan is None:
            plan = self.trainer.link_plan(
                state.g_params, n_links=self.trainer.fc.cohort_size())
        p = state.round + 1
        self._top_up(state, plan)
        held_plan, handle = self._pending.pop(p)
        if held_plan is not plan and held_plan != plan:
            handle = None          # plan changed since dispatch: re-draw
        if handle is not None:
            self.collected += 1
        state, rec = self.trainer.round_once(
            state, xs["dev_x"], xs["dev_y"], xs["test_x"], xs["test_y"],
            plan=plan, log=xs.get("log"), _pending_link=handle)
        return state, rec

    def finalize(self) -> dict:
        """Drop any still-in-flight draws and report dispatch stats."""
        stats = {"dispatched": self.dispatched,
                 "collected": self.collected,
                 "abandoned": len(self._pending),
                 "pipeline_depth": self.options.pipeline_depth}
        self._pending.clear()
        return stats


class GridRoundProgram:
    """The sweep engine's compiled program behind the same face.

    ``step_fn(state, xs)`` is the jitted whole-grid scan (state: a
    grid-layout :class:`RoundState`; xs: the engine's stacked per-round
    arrays); ``build`` happened in the engine (tracing is its
    ``engine_stats`` counter).  ``finalize`` blocks and returns the
    scanned outputs host-side.
    """

    def __init__(self, step_fn: Callable, state0: RoundState,
                 options: Optional[ProgramOptions] = None):
        self._step_fn = step_fn
        self.options = options or ProgramOptions()
        self.state = RoundState.from_mapping(state0)
        self._out: Any = None

    def step(self, state, xs):
        """Run the compiled scan over all rounds (the grid path's unit
        of work is the whole schedule, not one round)."""
        state = RoundState.from_mapping(state)
        new_state, out = self._step_fn(state, xs)
        self.state, self._out = new_state, out
        return new_state, out

    def finalize(self):
        import numpy as np
        jax.block_until_ready(self.state.g_params)
        return self.state, jax.tree.map(np.asarray, self._out)
