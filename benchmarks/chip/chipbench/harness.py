"""Runs one cell of ``BENCHMARK.json``: set-up, the measured window, the
check against the reference, and the result line.

Everything that belongs to one cell is found by name:
``BENCHMARK.json`` names the cell's configuration and traffic;
``configs/``, ``traffic/`` and ``workloads/<cell>.json`` hold their
parameters; the workload names the driver (``drivers/<driver>.py``);
and every per-layer metric is read by ``metrics/<metric>.py``.
"""
from __future__ import annotations

import gc
import hashlib
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]     # benchmarks/chip
ROOT = CHIP.parents[1]                          # the checkout
OUT = CHIP / "out"                              # git-ignored run output


def program_seed(seed: int) -> int:
    """A 31-bit seed for the program (``PRNGKey`` keeps only 32 bits of
    a Python int), hashed from all bits of ``--seed``."""
    digest = hashlib.sha256(str(int(seed)).encode()).digest()
    return int.from_bytes(digest[:4], "little") & 0x7FFFFFFF


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def cell_spec(name: str, root: Path = ROOT) -> dict:
    """The cell's entry and files, looked up by name."""
    bench = load_json(root / "BENCHMARK.json")
    chip = root / bench["paths"][0]
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    entry = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name])]
    return {"name": name, "entry": entry, "chip": chip,
            "config": load_json(root / configs[entry["config"]]["file"]),
            "traffic": load_json(chip / "traffic" /
                                 f"{entry['traffic']}.json"),
            "workload": load_json(chip / "workloads" / f"{name}.json"),
            "end_to_end": e2e, "per_layer": layer}


def load_module(kind: str, name: str, chip: Path = CHIP):
    """``<kind>/<name>.py`` under the benchmark directory."""
    path = Path(chip) / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}", path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no {kind} file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class ProgramCounter:
    """Counts the programs JAX lowers (one per new jitted shape or static
    value, so a window that lowers none runs compiled code only) and
    sums where set-up spends its compile time: tracing, lowering, and
    compiling or loading from the persistent cache."""
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    SECONDS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
               LOWER: "lower_s",
               "/jax/core/compile/backend_compile_duration": "compile_s",
               "/jax/compilation_cache/cache_retrieval_time_sec":
               "cache_load_s"}
    COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax
        self.n = 0
        self.sums = dict.fromkeys(
            [*self.SECONDS.values(), *self.COUNTS.values()], 0)
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event == self.LOWER:
            self.n += 1
        if event in self.SECONDS:
            self.sums[self.SECONDS[event]] += duration

    def _on_event(self, event, **_):
        if event in self.COUNTS:
            self.sums[self.COUNTS[event]] += 1

    def report(self) -> str:
        return " ".join(f"{k}={v:.3f}" if isinstance(v, float) else
                        f"{k}={v}" for k, v in self.sums.items())


class Context:
    """What a driver's ``setup`` gets: the cell's files, the seeds and
    where to write."""

    def __init__(self, spec: dict, seed: int):
        import jax
        self.spec = spec
        self.config = spec["config"]
        self.traffic = spec["traffic"]
        self.workload = spec["workload"]
        self.seed = program_seed(seed)
        self.data_key = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                           0xDA7A)
        self.out = OUT / spec["name"]
        self.t0 = time.perf_counter()

    def mark(self, what: str) -> None:
        """Log a set-up phase's end on standard error."""
        print(f"[setup] {what} at {time.perf_counter() - self.t0:.3f} s",
              file=sys.stderr, flush=True)


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def require_chips(chips: int) -> str | None:
    """None when JAX sees at least ``chips`` TPU chips, else why not."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"no TPU: the first device is {devs[0].platform!r}"
    if len(devs) < chips:
        return f"the cell needs {chips} chips, JAX sees {len(devs)}"
    return None


def measure(cell, seconds: float) -> dict:
    """Steps ``cell`` until ``seconds`` have passed; the rate is taken
    over all the rounds and all the time of the window.  Each step's
    host time and the collector's passes are kept, to name a slow run's
    stall."""
    import jax
    rounds = steps = 0
    marks = []
    gc_s = []

    def on_gc(phase, info):
        if phase == "start":
            gc_s.append(-time.perf_counter())
        elif gc_s:
            gc_s[-1] += time.perf_counter()

    gc.callbacks.append(on_gc)
    try:
        with jax.profiler.TraceAnnotation("bench_window"):
            t0 = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("bench_step"):
                    rounds += cell.step()
                steps += 1
                marks.append(time.perf_counter())
                if marks[-1] - t0 >= seconds:
                    break
            cell.sync()
            elapsed = time.perf_counter() - t0
    finally:
        gc.callbacks.remove(on_gc)
    step_s = [b - a for a, b in zip([t0] + marks[:-1], marks)]
    return {"rounds": rounds, "steps": steps, "window_s": elapsed,
            "step_s": step_s, "gc_s": gc_s}


def step_report(meas: dict) -> str:
    """The window's step times on the host: median, the slowest three
    (index and seconds) and the collector's passes."""
    import statistics
    times = meas["step_s"]
    slow = sorted(range(len(times)), key=times.__getitem__)[-3:][::-1]
    return (f"step_s median={statistics.median(times):.6f} slowest="
            + ",".join(f"{i}:{times[i]:.6f}" for i in slow)
            + f" gc_passes={len(meas['gc_s'])}"
            f" gc_s={sum(meas['gc_s']):.6f}")


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True, spec=None,
             on_trace=None) -> tuple[int, dict | None]:
    """Runs the cell; returns (exit code, result).  A run that finds no
    chip, or too few, returns a non-zero code and no result.
    ``on_trace``, where given, gets what the metric readers read in a
    traced run (``tests/record_trace.py`` keeps it as test data)."""
    import jax

    from chipbench import checks, peaks, xtrace

    spec = spec or cell_spec(name)
    chips = int(spec["entry"]["chips"])
    if require_chip:
        why = require_chips(chips)
        if why:
            print(f"chip benchmark: {why}; refusing to run", file=sys.stderr)
            return 2, None
    programs = ProgramCounter()
    ctx = Context(spec, seed)
    driver = load_module("drivers", spec["workload"]["driver"], spec["chip"])
    cell = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    lowered_setup = programs.n
    print(f"[setup] setup_s={setup_s:.3f} before_cell_s="
          f"{ctx.t0 - t_start:.3f} {programs.report()}", file=sys.stderr,
          flush=True)

    window = seconds
    trace_dir = OUT / spec["name"] / "trace"
    if trace:
        window = min(seconds, float(spec["workload"]["trace_seconds"]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    meas = measure(cell, window)
    if trace:
        jax.profiler.stop_trace()
    lowered = programs.n - lowered_setup
    print(f"[window] rounds={meas['rounds']} window_s={meas['window_s']} "
          f"programs_lowered_in_window={lowered} "
          f"programs_lowered_in_setup={lowered_setup}", flush=True)
    print(f"[window] {step_report(meas)}", file=sys.stderr, flush=True)

    device = device_info(chips)
    attempted, failed = meas["rounds"], cell.failed
    layer_run = {"rounds": meas["rounds"], "window_s": meas["window_s"],
                 "chips": chips, "flops_per_round": cell.flops_per_round,
                 "programs": cell.programs, "kernel": cell.kernel,
                 "saves_ms": list(getattr(cell, "saves_ms", []))}
    cell.release()
    gc.collect()

    t_ref = time.perf_counter()
    try:
        numbers = checks.compare(cell.program_records(),
                                 cell.reference_records())
    except checks.MissingOutput as e:
        print(f"[check] {e}", file=sys.stderr)
        numbers = {}
    print(f"[check] reference_s={time.perf_counter() - t_ref}",
          file=sys.stderr, flush=True)
    correct, rows = checks.judge(numbers, spec["workload"]["limits"])
    correct = correct and lowered == 0 and failed == 0

    metrics = {}
    breakdown = None
    if trace:
        record = xtrace.extract(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        layer_run["trace"] = record
        layer_run["peaks"] = peaks.peaks(device["kind"])
        device["busy_s"] = xtrace.busy_s(record)
        device["window_s"] = xtrace.window_s(record)
        for m in spec["per_layer"]:
            value = load_module("metrics", m["name"],
                                spec["chip"]).read(layer_run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = xtrace.breakdown(record)
        if on_trace is not None:
            on_trace({**layer_run, "device_kind": device["kind"]})
    else:
        rate = meas["rounds"] / meas["window_s"]
        values = {"rounds_per_s": rate, "setup_s": setup_s}
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    for k, r in rows.items():
        print(f"check {k} = {r['value']!r} limit {r['limit']!r}",
              file=sys.stderr)
    print(f"check programs_lowered_in_window = {lowered} limit 0",
          file=sys.stderr, flush=True)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["programs_lowered_in_window"] = lowered
    result["checks"] = {**rows, "programs_lowered_in_window":
                        {"value": lowered, "limit": 0}}
    return 0, result

