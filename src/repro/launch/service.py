"""Continuous-serving federated round driver: train forever, survive
SIGKILL, answer inference traffic between rounds.

``FederatedTrainer.run`` is a terminate-and-exit script; this module
drives the same factored round step (:meth:`FederatedTrainer.round_once`)
as a long-running service:

* **Churn** — devices arrive and depart between rounds.  The active
  cohort of round ``p`` is drawn by a *stateless* seeded host process
  (``np.random.default_rng([fc.seed, churn.seed, p, MECH_CHURN])`` —
  the mechanism tag keeps churn's stream disjoint from the client
  sampler's), so the cohort sequence is a pure function of the round
  number: a resumed run draws the exact cohorts the uninterrupted run
  would have, with no RNG state to checkpoint.
* **Straggler timeouts** — enabled through the channel config
  (``compute_mean_s``/``deadline_s``): the :class:`LinkPlan` draw masks
  devices past the round deadline out of the aggregation set exactly
  like uplink outages (see ``channel.pipeline``).
* **Checkpoint/restore** — every ``ckpt_every`` rounds the full
  resumable state (round PRNG key, global + per-device params,
  ``gout``/``dev_gout``, the convergence reference, the round-1 seed
  set) goes through the crash-safe ``checkpoint`` package, with the
  host-side scalars (round counter, cumulative time, converged round,
  DP accountant position, per-round history) in the manifest ``meta``.
  A SIGKILLed run restores from the latest complete step directory and
  continues the *bit-identical* PRNG stream: every per-round draw
  derives from ``fold_in(key, p)``, and both ``key`` and ``p`` are in
  the checkpoint.
* **Batched inference** — :class:`InferenceEndpoint` serves the current
  global model between rounds with a fixed-batch jitted apply (the CNN
  single-shot analogue of ``launch.serve``'s prefill step: one compiled
  shape, requests padded to it, so serving never retraces).

With churn and stragglers disabled the per-round records equal
``FederatedTrainer.run``'s history bit-for-bit — locked down in
tests/test_service.py.

CLI smoke (checkpoint + kill + resume + one served batch)::

    PYTHONPATH=src python -m repro.launch.service --rounds 4 \
        --ckpt-dir /tmp/fedsvc --verify-resume
"""
from __future__ import annotations

import argparse
import tempfile
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.channel import ChannelConfig
from repro.core.privacy import GaussianAccountant
from repro.core.program import (LoopRoundProgram, ProgramOptions,
                                tree_nbytes)
from repro.core.protocols import (FederatedConfig, FederatedTrainer,
                                  summarize_seeds)
from repro.core.sampling import ChurnConfig
from repro.core.state import RoundState
from repro.launch.compile_cache import enable_compile_cache

__all__ = ["ChurnConfig", "FederatedService", "InferenceEndpoint"]

#: Keys of one round's JSON-ready history record (the ``link`` arrays
#: stay out of the checkpoint meta).
_RECORD_KEYS = ("round", "acc", "loss", "round_latency_s", "compute_s",
                "cum_time_s", "uplink_ok", "n_straggle")


class InferenceEndpoint:
    """Fixed-batch jitted inference over the current global model.

    The serving shape mirrors ``launch.serve``: one compiled step at a
    fixed batch size (the prefill analogue — the CNN is single-shot, so
    there is no decode loop), with incoming requests queued and padded
    to that shape.  ``submit`` enqueues feature arrays; ``flush`` runs
    as many padded batches as the queue holds and returns per-request
    predicted labels in submission order.

    ``input_shape`` (normally the serving task's
    ``TaskSpec.input_shape``) pins the per-request feature shape; a
    mis-shaped request is rejected at ``submit`` time with both sides
    named, instead of surfacing as a retrace or a model-side shape
    error mid-flush.
    """

    def __init__(self, apply_fn, batch_size: int = 16,
                 input_shape: Optional[tuple] = None):
        self.batch_size = batch_size
        self.input_shape = tuple(input_shape) if input_shape else None
        self._queue: list = []
        self.served = 0
        self.batches = 0

        def predict(params, x):
            return jnp.argmax(apply_fn(params, x), axis=-1)

        self._predict = jax.jit(predict)

    def submit(self, x) -> int:
        """Queue a request batch ``(n, ...)``; returns n."""
        x = np.asarray(x)
        if self.input_shape is not None and \
                tuple(x.shape[1:]) != self.input_shape:
            raise ValueError(
                f"endpoint serves a model built for input shape "
                f"{self.input_shape} but got a request batch of shape "
                f"{tuple(x.shape[1:])}")
        self._queue.extend(x)
        return x.shape[0]

    @property
    def pending(self) -> int:
        return len(self._queue)

    def flush(self, g_params) -> np.ndarray:
        """Serve every pending request against ``g_params``.  Requests
        are padded to the fixed batch shape (pad rows are discarded), so
        the jitted step never retraces.

        Failure-safe: results only reach the caller if every chunk
        predicts, so if predict raises mid-loop NO request was answered
        — the whole flushed queue is re-queued (ahead of anything
        submitted meanwhile) before the exception propagates.  A
        crashed flush loses no requests: the next flush serves them
        all, in submission order.  (Re-queueing only the unreached tail
        here used to leak the already-predicted chunks — their results
        never left this frame.)"""
        if not self._queue:
            return np.zeros((0,), np.int32)
        out = []
        B = self.batch_size
        queue, self._queue = self._queue, []
        try:
            for i in range(0, len(queue), B):
                chunk = np.stack(queue[i:i + B])
                n = chunk.shape[0]
                if n < B:
                    pad = np.zeros((B - n,) + chunk.shape[1:],
                                   chunk.dtype)
                    chunk = np.concatenate([chunk, pad])
                preds = np.asarray(self._predict(g_params,
                                                 jnp.asarray(chunk)))[:n]
                out.append(preds)
                self.batches += 1
        except BaseException:
            self._queue[:0] = queue
            raise
        preds = np.concatenate(out)
        self.served += preds.shape[0]
        return preds


class FederatedService:
    """Crash-safe continuous round driver over a device pool.

    ``pool_x``/``pool_y`` are the *full* population's shards
    ``(P, n_local, ...)``; each round trains the churned active cohort
    through :meth:`FederatedTrainer.round_once` and scatters the
    cohort's updated device state back into the pool.  ``step()`` runs
    one round; :meth:`run_rounds` drives N of them with periodic
    checkpoints; :meth:`restore` resumes from the newest complete
    checkpoint in ``ckpt_dir``.
    """

    def __init__(self, model, fc: FederatedConfig,
                 ch: Optional[ChannelConfig] = None, *,
                 churn: Optional[ChurnConfig] = None,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 1,
                 keep: Optional[int] = None, serve_batch: int = 16,
                 options: Optional[ProgramOptions] = None):
        if fc.model_partition is not None:
            raise ValueError(
                "FederatedService drives homogeneous cohorts: churn "
                "gathers/scatters one (P, ...) device stack, which a "
                "mixed-architecture cohort's per-architecture stacks "
                "don't fit; run mixed cohorts through FederatedTrainer "
                "or the sweep engine")
        self.trainer = FederatedTrainer(model, fc, ch)
        self.fc = self.trainer.fc
        # explicit churn wins, then the config's own churn sub-config
        self.churn = churn or self.fc.churn or ChurnConfig()
        self.options = options or ProgramOptions()
        # the unified round program: at pipeline_depth > 1 future rounds'
        # link draws are dispatched while the current round trains; the
        # per-round plan rides in xs, so a churn-driven cohort-size
        # change invalidates (and cheaply re-draws) stale handles
        self._program = LoopRoundProgram(self.trainer, self.options)
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.keep = keep
        # the served batch shape comes from the config's task, so a
        # model=None service (registry-built) serves the right geometry
        self.endpoint = InferenceEndpoint(
            self.trainer.model.apply, serve_batch,
            input_shape=self.fc.task_spec().input_shape)
        spec = self.fc.codec_spec()
        # effective participation fraction: churn and client sampling
        # compose (round_once sub-samples the churned cohort)
        q = self.churn.p_active * self.fc.sample_ratio
        self._acct = (GaussianAccountant(spec.dp_sigma, spec.dp_delta,
                                         sample_ratio=q)
                      if spec.name == "dp_gaussian" else None)
        self.state = self.trainer.init_state()
        self.history: list[dict] = []
        self._data = None
        self._seed_meta = None  # summarize_seeds of the round-1 set

    # -- data binding --------------------------------------------------
    def bind_data(self, pool_x, pool_y, test_x, test_y):
        """Attach the device pool and eval set (kept out of checkpoints:
        data re-binds on process start, state restores from disk)."""
        pool_x, pool_y = jnp.asarray(pool_x), jnp.asarray(pool_y)
        if pool_x.shape[0] != self.fc.num_devices:
            raise ValueError(
                f"pool has {pool_x.shape[0]} devices but the config "
                f"says num_devices={self.fc.num_devices}")
        self._data = (pool_x, pool_y, jnp.asarray(test_x),
                      jnp.asarray(test_y))
        return self

    # -- one round -----------------------------------------------------
    def step(self, log=None) -> dict:
        """One federated round over the churned active cohort; returns
        the round record (plus cohort bookkeeping)."""
        if self._data is None:
            raise RuntimeError("call bind_data(...) before step()")
        pool_x, pool_y, test_x, test_y = self._data
        state = RoundState.from_mapping(self.state)
        p = state.round + 1
        with jax.profiler.TraceAnnotation("cohort_io", round=p) as span:
            idx = self.churn.active_devices(self.fc.seed, p,
                                            self.fc.num_devices)
            jdx = jnp.asarray(idx)
            cohort = state.replace(
                dev_params=jax.tree.map(lambda a: a[jdx],
                                        state.dev_params),
                dev_gout=state.dev_gout[jdx])
            dev_x, dev_y = pool_x[jdx], pool_y[jdx]
            span.set_metadata(bytes=tree_nbytes(
                cohort.dev_params, cohort.dev_gout, dev_x, dev_y))
        plan = self.trainer.link_plan(state.g_params, n_links=len(idx))
        cohort, rec = self._program.step(
            cohort, {"dev_x": dev_x, "dev_y": dev_y,
                     "test_x": test_x, "test_y": test_y, "plan": plan,
                     "log": log})
        # scatter the cohort's device state back into the pool; shared
        # (global) fields carry over wholesale
        with jax.profiler.TraceAnnotation(
                "cohort_io", round=p,
                bytes=tree_nbytes(cohort.dev_params, cohort.dev_gout)):
            self.state = cohort.replace(
                dev_params=jax.tree.map(
                    lambda pool, coh: pool.at[jdx].set(coh),
                    state.dev_params, cohort.dev_params),
                dev_gout=state.dev_gout.at[jdx].set(cohort.dev_gout))
        # actual participants: the churned cohort, further narrowed by
        # round_once's client sampling when fc.sample_ratio < 1
        # (rec["cohort"] indexes within the churned cohort)
        active = idx if rec["cohort"] is None else idx[rec["cohort"]]
        if self._acct is not None:
            # privacy budget is spent by participating devices only
            self._acct.step(cohort=active)
            rec["dp_epsilon"] = self._acct.epsilon()
            rec["dp_epsilon_device_max"] = self._acct.epsilon_device_max()
        rec["n_active"] = len(active)
        rec["active"] = active
        self.history.append(rec)
        if self.ckpt_dir and p % self.ckpt_every == 0:
            self.save_checkpoint()
        return rec

    def run_rounds(self, n: int, log=None) -> list[dict]:
        """Drive ``n`` rounds (the CLI's --rounds; a real deployment
        loops step() forever)."""
        return [self.step(log=log) for _ in range(n)]

    # -- serving -------------------------------------------------------
    def serve(self, x) -> np.ndarray:
        """Answer one inference request batch against the current
        global model (between rounds, training state untouched)."""
        self.endpoint.submit(x)
        return self.endpoint.flush(self.state.g_params)

    # -- checkpoint / restore -----------------------------------------
    def _history_meta(self) -> list[dict]:
        return [{k: r.get(k) for k in
                 _RECORD_KEYS + ("n_active", "dp_epsilon",
                                 "dp_epsilon_device_max")
                 if k in r} for r in self.history]

    def save_checkpoint(self) -> str:
        """Write the full resumable state.  Array state goes in the
        (atomically renamed) step dir; host scalars ride in the manifest
        meta.  ``prev`` is absent only before the first round."""
        if not self.ckpt_dir:
            raise RuntimeError("service has no ckpt_dir")
        state = RoundState.from_mapping(self.state)
        with jax.profiler.TraceAnnotation("checkpoint", round=state.round):
            tree = {"key": np.asarray(state.key),
                    "g_params": state.g_params,
                    "dev_params": state.dev_params,
                    "gout": state.gout,
                    "dev_gout": state.dev_gout}
            if state.prev is not None:
                tree["prev"] = state.prev
            if state.seeds is not None:
                tree["seeds"] = {"train_x": state.seeds["train_x"],
                                 "train_y": state.seeds["train_y"]}
            if self._seed_meta is None and state.seeds is not None \
                    and "uploaded" in state.seeds:
                # the full round-1 dict is only in memory on the run that
                # collected it; its summary rides along in every checkpoint
                self._seed_meta = summarize_seeds(state.seeds)
            meta = {"round": state.round,
                    "cum_time_s": state.cum_time_s,
                    "converged_round": state.converged_round,
                    "protocol": self.fc.protocol,
                    "dp_rounds": (self._acct.rounds
                                  if self._acct is not None else 0),
                    # dense per-device participation counts as a flat int
                    # list — compact at pool scale, unlike a str-keyed dict
                    "dp_device_counts": (
                        self._acct.device_counts.tolist()
                        if self._acct is not None else None),
                    "seed_meta": self._seed_meta,
                    "history": self._history_meta()}
            return checkpoint.save(self.ckpt_dir, state.round, tree,
                                   meta=meta, keep=self.keep)

    def restore(self, step: Optional[int] = None) -> int:
        """Rebuild the resumable state from the newest (or ``step``-th)
        checkpoint; returns the restored round number.  Bit-identical
        continuation: the round key and counter come straight off disk,
        and every in-round draw is derived from them."""
        if not self.ckpt_dir:
            raise RuntimeError("service has no ckpt_dir")
        tree, meta = checkpoint.restore_tree(self.ckpt_dir, step)
        seeds = None
        if "seeds" in tree:
            seeds = {"train_x": jnp.asarray(tree["seeds"]["train_x"]),
                     "train_y": jnp.asarray(tree["seeds"]["train_y"])}
        # checkpoint manifest keys ARE RoundState fields (1:1); the
        # array tree holds the device-resident fields, the manifest meta
        # the host scalars
        self.state = RoundState(
            round=meta["round"],
            key=jnp.asarray(tree["key"]),
            g_params=jax.tree.map(jnp.asarray, tree["g_params"]),
            dev_params=jax.tree.map(jnp.asarray, tree["dev_params"]),
            gout=jnp.asarray(tree["gout"]),
            dev_gout=jnp.asarray(tree["dev_gout"]),
            prev=(jnp.asarray(tree["prev"]) if "prev" in tree
                  else None),
            converged_round=meta["converged_round"],
            seeds=seeds,
            cum_time_s=meta["cum_time_s"],
        )
        self.history = list(meta.get("history", []))
        # draws dispatched before the restore point are stale (they were
        # keyed off rounds this process will now re-run with possibly
        # different cohort plans) — drop the whole window; re-drawing is
        # cheap and the keys are pure functions of (key, round) anyway
        self._program.finalize()
        self._seed_meta = meta.get("seed_meta")
        if self._acct is not None:
            self._acct.rounds = meta.get("dp_rounds", 0)
            counts = meta.get("dp_device_counts")
            if counts is not None:
                self._acct.device_counts = np.asarray(counts, np.int64)
            else:
                # pre-array checkpoints stored a str-keyed dict
                self._acct.device_rounds = {
                    int(k): int(v) for k, v in
                    (meta.get("dp_device_rounds") or {}).items()}
        return meta["round"]


# ---------------------------------------------------------------------------
# CLI smoke: N rounds with checkpoints, one served batch, optional
# kill-free resume verification (restore an earlier step, re-run the
# tail, compare records) — the CI sweeps job runs this.
# ---------------------------------------------------------------------------

def _smoke_setup(args):
    from repro.data import partition_iid
    from repro.data.pipeline import parse_task

    # the task fixes data geometry and class count; the model comes from
    # the registry (defaults reproduce the historical CNN-on-digits
    # smoke bit-for-bit: same generator, same init stream)
    task = parse_task(getattr(args, "task", "digits"))
    x, y = task.data(jax.random.PRNGKey(42), 1400)
    dev_x, dev_y = partition_iid(np.asarray(x[:1200]),
                                 np.asarray(y[:1200]), 4, 300,
                                 task.num_classes, seed=0)
    fc = FederatedConfig(protocol=args.protocol, num_devices=4,
                         local_iters=8, local_batch=16, server_iters=8,
                         server_batch=16, max_rounds=args.rounds,
                         n_seed=6, n_inverse=12, seed=0,
                         model=getattr(args, "model", "cnn"),
                         task=task.name)
    ch = ChannelConfig(num_devices=4, p_up_dbm=40.0,
                       compute_mean_s=args.compute_mean_s,
                       deadline_s=args.deadline_s)
    churn = ChurnConfig(p_active=args.p_active, min_active=2)
    opts = ProgramOptions(
        pipeline_depth=getattr(args, "pipeline_depth", 1))
    svc = FederatedService(None, fc, ch, churn=churn,
                           ckpt_dir=args.ckpt_dir, ckpt_every=1,
                           options=opts)
    svc.bind_data(dev_x, dev_y, x[1200:], y[1200:])
    return svc, (x, y)


def _tail(records):
    return [{k: r[k] for k in ("round", "acc", "loss", "round_latency_s",
                               "uplink_ok")} for r in records]


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="continuous federated service smoke")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--protocol", default="mix2fld")
    ap.add_argument("--model", default="cnn",
                    help="registry model to train/serve (cnn/mlp/"
                         "transformer; homogeneous only)")
    ap.add_argument("--task", default="digits",
                    help="registry task shaping the synthetic workload")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--p-active", type=float, default=0.75)
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    dest="pipeline_depth",
                    help="rounds of link draws in flight (1 = strict "
                         "serial; 2 = double-buffered channel sim)")
    ap.add_argument("--compute-mean-s", type=float, default=0.05,
                    dest="compute_mean_s")
    ap.add_argument("--deadline-s", type=float, default=0.15,
                    dest="deadline_s")
    ap.add_argument("--verify-resume", action="store_true",
                    help="restore the halfway checkpoint into a fresh "
                         "service, re-run the tail, and require "
                         "identical per-round records")
    return ap


def main(argv=None) -> int:
    args = _parser().parse_args(argv)
    enable_compile_cache()
    if args.ckpt_dir is None:
        args.ckpt_dir = tempfile.mkdtemp(prefix="fedsvc_")

    svc, _ = _smoke_setup(args)
    recs = svc.run_rounds(args.rounds, log=print)
    n_straggled = sum(r["n_straggle"] for r in recs)
    pstats = svc._program.finalize()
    print(f"trained {args.rounds} rounds: final acc={recs[-1]['acc']:.3f}"
          f" cohort sizes={[r['n_active'] for r in recs]}"
          f" stragglers dropped={n_straggled}"
          f" pipeline={pstats}")

    # one served batch against the live global model
    pool_x = np.asarray(svc._data[0])
    preds = svc.serve(pool_x[0][: svc.endpoint.batch_size])
    print(f"served {preds.shape[0]} predictions "
          f"(endpoint batches={svc.endpoint.batches})")

    if args.verify_resume:
        mid = max(1, args.rounds // 2)
        svc2, _ = _smoke_setup(args)
        got = svc2.restore(step=mid)
        assert got == mid, (got, mid)
        tail = svc2.run_rounds(args.rounds - mid)
        want, have = _tail(recs[mid:]), _tail(tail)
        if want != have:
            print(f"RESUME MISMATCH:\n  want {want}\n  have {have}")
            return 1
        print(f"resume verified: rounds {mid + 1}..{args.rounds} "
              f"bit-identical after restore from step {mid}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
