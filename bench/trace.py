"""Profiler trace -> device busy time, per-name device time and idle gaps.

A traced run wraps its measured window in a host annotation named
``WINDOW``.  :func:`extract` reads the ``.xplane.pb`` that
``jax.profiler`` wrote into plain lists of ``(name, start_ns, dur_ns)``:

* ``ops``: per device plane, the events of its ``XLA Ops`` line (one per
  operation that ran on the device, Pallas kernels included), each named
  ``<program>/<instruction>`` after the program run that holds it;
* ``modules``: per device plane, the events of its ``XLA Modules`` line
  (one per run of a jitted program, named ``jit_<function>(...)``);
* ``host``: events of the host threads, for naming idle gaps.

:func:`reduce` turns that into the numbers the per-layer readers use.  It
works on the extracted lists only, so the tests check it on a small
recorded extract without a chip.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

WINDOW = "bench_window"
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


def extract(trace_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = {"ops": {}, "modules": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                key = {_OPS_LINE: "ops", _MODULES_LINE: "modules"}.get(line.name)
                if key is not None:
                    out[key][plane.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events if ev.duration_ns > 0)
    return label(out)


def program_name(module: str) -> str:
    """``jit_train_step(1589...)`` -> ``train_step``."""
    name = module.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def label(ex: dict) -> dict:
    """Name every op ``<program>/<instruction>``: an op event's name is its
    whole HLO instruction (``%fusion.75 = f32[...] fusion(...)``), and
    instruction names repeat across programs."""
    import bisect

    ops = {}
    for plane, events in ex["ops"].items():
        mods = sorted(ex["modules"].get(plane, []), key=lambda e: e[1])
        starts = [m[1] for m in mods]
        named = []
        for name, s, d in events:
            instr = name.split(" = ", 1)[0].lstrip("%")
            i = bisect.bisect_right(starts, s) - 1
            prog = (program_name(mods[i][0])
                    if i >= 0 and s < mods[i][1] + mods[i][2] else "?")
            named.append((f"{prog}/{instr}", s, d))
        ops[plane] = named
    return {**ex, "ops": ops}


def window_bounds(ex: dict) -> tuple[int, int]:
    """(start_ns, end_ns) of the ``WINDOW`` annotation on the host."""
    spans = [(s, s + d) for n, s, d in ex["host"] if n == WINDOW]
    if not spans:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _clip(events, t0: int, t1: int):
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def _union(intervals) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _host_activity(host, a: int, b: int) -> str:
    """What the host was doing while the device waited in [a, b): the
    shortest host event that covers at least half of the gap (the most
    specific one), else the one that overlaps it most."""
    covering, best, best_ov = None, "host idle", 0
    for name, s, d in host:
        if name == WINDOW:
            continue
        ov = min(s + d, b) - max(s, a)
        if ov <= 0:
            continue
        if 2 * ov >= b - a and (covering is None or d < covering[1]):
            covering = (name, d)
        if ov > best_ov:
            best, best_ov = name, ov
    return covering[0] if covering is not None else best


def reduce(ex: dict, top: int = 10) -> dict:
    """Busy and window seconds (busy averaged over the device planes that ran
    an operation), device seconds per op name and per program name, and the
    ``top`` longest device operations and idle gaps, all clipped to the
    window.  Programs are keyed by function name (:func:`program_name`)."""
    t0, t1 = window_bounds(ex)
    window_s = (t1 - t0) / 1e9
    busy = []
    op_s: dict[str, float] = defaultdict(float)
    gaps: list[tuple[int, int]] = []
    for plane, events in ex["ops"].items():
        clipped = list(_clip(events, t0, t1))
        if not clipped:
            continue
        for name, a, b in clipped:
            op_s[name] += (b - a) / 1e9
        spans = _union((a, b) for _, a, b in clipped)
        busy.append(sum(b - a for a, b in spans) / 1e9)
        edges = [t0] + [x for ab in spans for x in ab] + [t1]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    mod_s: dict[str, float] = defaultdict(float)
    for events in ex["modules"].values():
        for name, a, b in _clip(events, t0, t1):
            mod_s[program_name(name)] += (b - a) / 1e9
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "devices": len(busy),
        "op_s": dict(op_s),
        "module_s": dict(mod_s),
        "device_ops": sorted(op_s.items(), key=lambda kv: kv[1], reverse=True)[:top],
        "idle_gaps": [[_host_activity(ex["host"], a, b), (b - a) / 1e9]
                      for a, b in gaps[:top]],
    }


def seconds_matching(table: dict[str, float], needles) -> float:
    """Total seconds of the names in ``table`` that contain any needle."""
    return sum(v for k, v in table.items() if any(n in k for n in needles))
