"""From the profiler's ``.xplane.pb`` to a small record, and from that
record to device busy time, program launches and kernel time.

``extract`` keeps, from every device plane (``/device:...``), the
events of its ``XLA Modules`` line (one per program launch) and its
``XLA Ops`` line (one per operation), and from the host the events of
the thread that ran the window, found by the ``bench_window`` annotation
the harness writes there (with its ``bench_step`` annotations,
``PjitFunction(...)`` dispatches and waits).  Each
event is ``[name, start_ns, duration_ns, label]``, where ``label`` is
the op's ``tf_op`` / ``long_name`` stat when the profiler gives one.
The metric readers (``metrics/``) read only this record, so a recorded
one (``testdata/``) checks them without a chip.
"""
from __future__ import annotations

import glob
from pathlib import Path

DEVICE_LINES = ("XLA Modules", "XLA Ops")
HOST_MIN_NS = 10_000  # host events shorter than this are dropped
LABEL_STATS = ("tf_op", "long_name", "hlo_module")


def _label(event) -> str:
    try:
        stats = dict(event.stats)
    except (TypeError, ValueError):
        return ""
    for key in LABEL_STATS:
        if key in stats and stats[key]:
            return str(stats[key])[:300]
    return ""


def extract(trace_dir: str | Path) -> dict:
    """The record of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    out = {"device": {}, "host": [], "lines": {}}
    for plane in data.planes:
        lines = list(plane.lines)
        out["lines"][plane.name] = [ln.name for ln in lines]
        if plane.name.startswith("/device:"):
            keep = {}
            for ln in lines:
                if ln.name in DEVICE_LINES:
                    keep[ln.name] = [[e.name, int(e.start_ns),
                                      int(e.duration_ns), _label(e)]
                                     for e in ln.events]
            if keep:
                out["device"][plane.name] = keep
        else:
            for ln in lines:
                events = list(ln.events)
                # the thread that ran the window: the harness's own
                # annotations mark it, whatever the line is called
                if any(e.name == "bench_window" for e in events):
                    out["host"] += [[e.name, int(e.start_ns),
                                     int(e.duration_ns), ""]
                                    for e in events
                                    if e.duration_ns >= HOST_MIN_NS]
    return out


# ---------------------------------------------------------------------------
# Reductions the metric readers share
# ---------------------------------------------------------------------------

def window(record: dict) -> tuple[int, int]:
    """(start_ns, end_ns) of the harness's ``bench_window`` annotation."""
    for name, start, dur, _ in record["host"]:
        if name == "bench_window":
            return start, start + dur
    raise ValueError(f"the trace holds no bench_window annotation; "
                     f"its planes and lines: {record['lines']}")


def _clip(events, lo: int, hi: int):
    for ev in events:
        s, e = max(ev[1], lo), min(ev[1] + ev[2], hi)
        if e > s:
            yield ev, s, e


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals; returns them sorted, disjoint."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def device_events(record: dict, line: str):
    """{plane: events} of one device line, falling back to the modules
    line for planes without an ops line."""
    out = {}
    for plane, lines in record["device"].items():
        evs = lines.get(line) or lines.get("XLA Modules") or []
        if evs:
            out[plane] = evs
    return out


def busy_intervals(record: dict) -> dict:
    """{plane: merged busy intervals inside the window}: the union of
    the intervals in which an operation ran on that device."""
    lo, hi = window(record)
    return {plane: union((s, e) for _, s, e in _clip(evs, lo, hi))
            for plane, evs in device_events(record, "XLA Ops").items()}


def busy_s(record: dict) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    per = busy_intervals(record)
    if not per:
        return 0.0
    return sum(sum(e - s for s, e in iv) for iv in per.values()) \
        / len(per) / 1e9


def window_s(record: dict) -> float:
    lo, hi = window(record)
    return (hi - lo) / 1e9


def events_in_window(record: dict, line: str, match) -> list:
    """Clipped ``(name, start, end, label, plane)`` of the events of
    ``line`` whose name or label satisfies ``match``."""
    lo, hi = window(record)
    out = []
    for plane, evs in record["device"].items():
        for ev, s, e in _clip(evs.get(line, []), lo, hi):
            if match(ev[0], ev[3]):
                out.append((ev[0], s, e, ev[3], plane))
    return out


def breakdown(record: dict, top: int = 10) -> dict:
    """The device operations that took most time in the window, summed
    by name, and the longest idle gaps, each named by the host event
    that covered most of it."""
    lo, hi = window(record)
    per_op: dict = {}
    for evs in device_events(record, "XLA Ops").values():
        for ev, s, e in _clip(evs, lo, hi):
            per_op[ev[0]] = per_op.get(ev[0], 0.0) + (e - s) / 1e9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    host = [h for h in record["host"]
            if h[0] not in ("bench_window", "bench_step")]
    for plane, iv in busy_intervals(record).items():
        edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    gaps.sort(reverse=True)
    named = []
    for dur, s, e in gaps[:top]:
        best, cover = "no host event", 0
        for name, hs, hd, _ in host:
            ov = min(e, hs + hd) - max(s, hs)
            if ov > cover:
                best, cover = name, ov
        named.append([best, dur / 1e9])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}


def module_ms_per_round(run: dict, program: str | None):
    """Milliseconds per round, per chip, of the launches of the program
    whose module name holds ``program``; None where it never ran."""
    if not program or not run["rounds"]:
        return None
    evs = events_in_window(run["trace"], "XLA Modules",
                           lambda name, label: program in name)
    if not evs:
        return None
    planes = {ev[4] for ev in evs}
    total = sum(e - s for _, s, e, _, _ in evs) / 1e6
    return total / len(planes) / run["rounds"]
