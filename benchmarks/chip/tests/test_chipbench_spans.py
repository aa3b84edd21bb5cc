"""The readers of the program's own spans against traces recorded on a
TPU v5e chip with the spans in place (``testdata/<cell>.spans.trace.json.gz``,
cut by ``tests/record_trace.py``), each number worked out here a second
way; and against the traces recorded before the program had spans, where
each reader finds nothing and returns nothing."""
import gzip
import json

import pytest

import chipbench_tiny as tiny

from chipbench import harness, peaks, xtrace  # noqa: E402

DATA = tiny.CHIP / "testdata"
CELLS = ("paper-mix2fld-loop", "population-fd-service")
METRICS = ("link_draw_ms", "cohort_io_ms", "eval_ms", "ckpt_d2h_ms")
SPANS = ("cohort_io", "local_train", "link_draw", "aggregate", "convert",
         "downlink", "evaluate", "converge", "checkpoint", "checkpoint.d2h")


def load(name: str) -> dict:
    with gzip.open(DATA / f"{name}.trace.json.gz", "rt") as f:
        run = json.load(f)
    run["peaks"] = peaks.peaks(run.pop("device_kind"))
    return run


def read(metric: str, run: dict):
    return harness.load_module("metrics", metric).read(run)


@pytest.fixture(params=CELLS)
def cell(request):
    return request.param, load(f"{request.param}.spans")


def _per_step_ms(run, names):
    """Milliseconds per round of the spans named ``names``, summed step
    by step: every span lies inside the harness's ``bench_step`` that
    ran it."""
    host = run["trace"]["host"]
    steps = [h for h in host if h[0] == "bench_step"]
    total = 0
    for _, s0, d0, _ in steps:
        total += sum(d for name, s, d, _ in host
                     if name in names and s0 <= s and s + d <= s0 + d0)
    return total / 1e6 / run["rounds"]


@pytest.mark.parametrize("metric,names", [
    ("link_draw_ms", ("link_draw",)),
    ("cohort_io_ms", ("cohort_io",)),
    ("eval_ms", ("evaluate", "converge"))])
def test_round_span_readers(cell, metric, names):
    name, run = cell
    got = read(metric, run)
    listed = {m["name"]: m for m in json.loads(
        (tiny.CHIP.parents[1] / "BENCHMARK.json").read_text())["per_layer"]}
    if name not in listed[metric]["workloads"]:
        assert got is None
        return
    assert got == pytest.approx(_per_step_ms(run, names))
    assert got > 0.0


def test_ckpt_d2h_ms():
    run = load("population-fd-service.spans")
    host = run["trace"]["host"]
    saves = [h for h in host if h[0] == "checkpoint"]
    copies = [d for n, s, d, _ in host if n == "checkpoint.d2h"
              and any(c[1] <= s and s + d <= c[1] + c[2] for c in saves)]
    assert saves and len(copies) == len(saves)
    got = read("ckpt_d2h_ms", run)
    assert got == pytest.approx(sum(copies) / 1e6 / len(saves))
    # the copy is part of the save
    assert got <= min(c[2] for c in saves) / 1e6
    assert read("ckpt_d2h_ms", load("paper-mix2fld-loop.spans")) is None


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("name", CELLS)
def test_readers_find_nothing_without_spans(metric, name):
    """The traces recorded before the program wrote spans: what a run of
    a program without them gives."""
    assert read(metric, load(name)) is None


def test_longest_idle_gap_is_named_by_a_program_span(cell):
    _, run = cell
    gaps = xtrace.breakdown(run["trace"])["idle_gaps"]
    assert gaps and gaps[0][0] in SPANS, gaps


def test_spans_of_a_step_are_disjoint(cell):
    _, run = cell
    spans = sorted((h for h in run["trace"]["host"]
                    if h[0] in SPANS and h[0] != "checkpoint.d2h"),
                   key=lambda h: h[1])
    assert len(spans) >= 8 * run["rounds"]
    for a, b in zip(spans, spans[1:]):
        assert a[1] + a[2] <= b[1], (a[0], b[0])
