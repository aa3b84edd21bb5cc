"""The round paths' own tracing: host spans on JAX's profiler and device
name scopes in the lowered programs.

One file, because a profiler session belongs to the whole process.  A
tiny Mix2FLD job that trains two of four devices a round runs three
ways: through the loop program, through a service that saves one
checkpoint, and as a two-point sweep.  Each runs once without a profiler
session and once under ``jax.profiler.trace``; the spans are read back
from the ``.xplane.pb`` with ``jax.profiler.ProfileData``.
"""
import glob
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.channel import ChannelConfig
from repro.core.program import SCOPES, SPANS, LoopRoundProgram
from repro.core.protocols import FederatedConfig, FederatedTrainer
from repro.core.sampling import SamplerConfig
from repro.launch.service import FederatedService
from repro.sweep import SweepRunner, make_grid

ROUNDS = 2
PATHS = ("loop", "service", "sweep")
# a loop round's spans: the sampled cohort's gather and scatter, and
# link_draw twice (the draw dispatched, then collected); a service round
# adds its pool's churn gather and scatter
LOOP_ROUND = sorted(["cohort_io", "local_train", "link_draw", "link_draw",
                     "aggregate", "convert", "downlink", "cohort_io",
                     "evaluate", "converge"])
SERVICE_ROUND = sorted(LOOP_ROUND + ["cohort_io", "cohort_io"])


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    return (rng.random((4, 48, 28, 28, 1), np.float32),
            rng.integers(0, 10, (4, 48)).astype(np.int32),
            rng.random((64, 28, 28, 1), np.float32),
            rng.integers(0, 10, 64).astype(np.int32))


# the registry's MLP keeps the file's compile time down
FC = FederatedConfig(protocol="mix2fld", model="mlp", num_devices=4,
                     local_iters=2, local_batch=8, server_iters=2,
                     server_batch=8, max_rounds=ROUNDS, n_seed=4,
                     n_inverse=8, seed=3,
                     sampler=SamplerConfig(sample_ratio=0.5))
# stragglers on: the link draw dispatches its compute-time stage too
CH = ChannelConfig(num_devices=4, p_up_dbm=40.0, compute_mean_s=0.05,
                   deadline_s=0.5)


def _loop(data, _):
    dev_x, dev_y, tx, ty = data
    trainer = FederatedTrainer(None, FC, CH)
    state = trainer.init_state()
    prog = LoopRoundProgram(trainer).bind(dev_x=dev_x, dev_y=dev_y,
                                          test_x=tx, test_y=ty)
    recs = []
    for _ in range(ROUNDS):
        state, rec = prog.step(state)
        recs.append(rec)
    return state, recs


def _service(data, ckpt_dir):
    svc = FederatedService(None, FC, CH, ckpt_dir=str(ckpt_dir),
                           ckpt_every=ROUNDS)
    recs = svc.bind_data(*data).run_rounds(ROUNDS)
    return svc.state, recs


def _summary(state, recs):
    keys = ("round", "acc", "loss", "round_latency_s", "uplink_ok",
            "n_straggle")
    return ([{k: r[k] for k in keys} for r in recs],
            [np.asarray(x) for x in jax.tree.leaves(
                (state.g_params, state.dev_params, state.dev_gout))])


@pytest.fixture(scope="module")
def runs(data, tmp_path_factory):
    """Each path run without a profiler session, then again under one,
    each traced path inside a marker span named after it."""
    tmp = tmp_path_factory.mktemp("spans")
    runner = SweepRunner(None, make_grid(
        FC, ChannelConfig(num_devices=4, p_up_dbm=40.0), eta=(0.01, 0.02)),
        *data)
    paths = {"loop": _loop, "service": _service,
             "sweep": lambda *_: runner.run()}
    plain = {k: run(data, tmp / "plain") for k, run in paths.items()}
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1          # the program's own spans only
    opts.python_tracer_level = 0
    traced = {}
    with jax.profiler.trace(str(tmp / "trace"), profiler_options=opts):
        for k, run in paths.items():
            with jax.profiler.TraceAnnotation(k):
                traced[k] = run(data, tmp / "traced")
    (pb,) = glob.glob(str(tmp / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(pb).planes:
        for line in plane.lines:
            events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name in SPANS + PATHS]
    marks = {e[0]: e for e in events if e[0] in PATHS}
    spans = {k: sorted((e for e in events if e[0] in SPANS
                        and m[1] <= e[1] and e[2] <= m[2]),
                       key=lambda e: e[1])
             for k, m in marks.items()}
    return {"plain": plain, "traced": traced, "runner": runner,
            "ckpt": tmp / "traced", "spans": spans}


def _of_round(spans, p):
    return [s for s in spans if s[3].get("round") == p]


def test_every_span_name_is_written(runs):
    assert set(runs["spans"]) == set(PATHS)
    assert {s[0] for spans in runs["spans"].values()
            for s in spans} == set(SPANS)


def test_loop_rounds_carry_their_spans(runs):
    loop = runs["spans"]["loop"]
    for p in range(1, ROUNDS + 1):
        spans = _of_round(loop, p)
        assert sorted(s[0] for s in spans) == LOOP_ROUND, p
        assert {s[3]["links"] for s in spans if s[0] == "link_draw"} == {2}
    assert all("round" in s[3] for s in loop)


def test_service_rounds_carry_their_spans(runs):
    svc = runs["spans"]["service"]
    for p in range(1, ROUNDS + 1):
        spans = [s for s in _of_round(svc, p) if s[0] != "checkpoint"]
        assert sorted(s[0] for s in spans) == SERVICE_ROUND, p
        assert all(s[3]["bytes"] > 0 for s in spans
                   if s[0] == "cohort_io")
    (ck,) = [s for s in svc if s[0] == "checkpoint"]
    assert ck[3]["round"] == ROUNDS


def test_spans_of_a_round_are_disjoint_and_none_covers_it(runs):
    for path in ("loop", "service"):
        for p in range(1, ROUNDS + 1):
            spans = _of_round(runs["spans"][path], p)
            for a, b in zip(spans, spans[1:]):
                assert a[2] <= b[1], (path, p, a[0], b[0])
            lo, hi = spans[0][1], max(s[2] for s in spans)
            assert not any(s[1] <= lo and s[2] >= hi for s in spans)


def test_checkpoint_copy_nests_in_the_save(runs):
    svc = runs["spans"]["service"]
    (ck,) = [s for s in svc if s[0] == "checkpoint"]
    (d2h,) = [s for s in svc if s[0] == "checkpoint.d2h"]
    assert ck[1] <= d2h[1] and d2h[2] <= ck[2]
    (npz,) = glob.glob(str(runs["ckpt"] / "step_*" / "arrays.npz"))
    with np.load(npz) as saved:
        assert d2h[3]["bytes"] == sum(saved[k].nbytes for k in saved)


def test_sweep_group_span(runs):
    (grp,) = runs["spans"]["sweep"]
    assert grp[0] == "sweep_group"
    assert grp[3] == {"points": 2, "rounds": ROUNDS}


def test_histories_are_the_same_with_and_without_the_profiler(runs):
    plain, traced = runs["plain"], runs["traced"]
    for path in ("loop", "service"):
        recs_a, leaves_a = _summary(*plain[path])
        recs_b, leaves_b = _summary(*traced[path])
        assert recs_a == recs_b
        for a, b in zip(leaves_a, leaves_b, strict=True):
            assert np.array_equal(a, b)
    for field in ("acc", "loss", "latency_s", "up_ok"):
        assert np.array_equal(getattr(plain["sweep"], field),
                              getattr(traced["sweep"], field))


def _scopes(text: str) -> set:
    """The name-scope components of a lowered program's locations, with
    transform wrappers (``vmap(...)``) peeled and the ``jit(...)`` of a
    jitted function's own name left out."""
    found = set()
    for loc in re.findall(r'loc\("([^"]+)"', text):
        for part in loc.split("/"):
            if part.startswith("jit("):
                continue
            while (m := re.fullmatch(r"\w+\((.*)\)", part)):
                part = m.group(1)
            found.add(part)
    return found


def test_lowered_programs_hold_the_scopes(data, runs):
    dev_x, dev_y, _, _ = data
    trainer = FederatedTrainer(None, FC, CH)
    state = trainer.init_state()
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    text = trainer._local_train.lower(
        state.dev_params, dev_x, dev_y, keys, state.dev_gout,
        np.asarray(True)).as_text(debug_info=True)
    assert "local_train" in _scopes(text)

    prog = runs["runner"]._programs[0][2]
    text = prog._step_fn.lower(prog._state0, prog._xs).as_text(
        debug_info=True)
    assert set(SCOPES) <= _scopes(text)


def test_scope_names_are_documented():
    doc = (Path(__file__).parents[1] / "docs" / "tracing.md").read_text()
    for name in SPANS + SCOPES:
        assert f"`{name}`" in doc
