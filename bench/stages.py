#!/usr/bin/env python3
"""Where a traced run's device idle went, by the program's own host spans.

    python3 bench/stages.py --workload <cell> --seed <n> --seconds <s>
        [--save-extract PATH]

Runs the cell once, as ``run.py --trace 1`` does, and reads the program's
spans (``repro.obs``: host events named ``gwlz.``) from the same trace.  The
last line of standard output is the run's result object with ``stages``
added (:func:`reduce`); ``--save-extract`` also writes the first 0.5 s of
the window's extract, spans included, as a recorded trace for the tests.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace  # noqa: E402

PREFIX = "gwlz."
# the spans around one whole operation of a window; idle is put down to
# the spans of the thread that holds them
OPERATIONS = ("gwlz.ingest", "gwlz.decode")
# span-name prefix of each layer (PERF.md's layer table)
LAYERS = {"entropy": "gwlz.entropy.", "executor": "gwlz.ingest",
          "training": "gwlz.train", "decode": "gwlz.decode"}
UNNAMED = "-"
EXTRACT_SECONDS = 0.5


def extract_spans(trace_dir: str) -> list:
    """``[name, thread, start_ns, dur_ns, nbytes]`` of every host event named
    ``gwlz.…`` in the newest ``.xplane.pb`` under ``trace_dir``; ``thread``
    names the host line (one per thread), ``nbytes`` is the annotation's."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    nbytes = dict(ev.stats).get("nbytes", 0)
                    out.append([ev.name, f"{plane.name}/{i}", int(ev.start_ns),
                                int(ev.duration_ns), int(nbytes)])
    return out


def _innermost(spans, t0: int, t1: int) -> list[tuple[int, int, str]]:
    """Cover ``[t0, t1)`` with segments named by the innermost span open in
    them: the one that started last (the shorter on a tie), else UNNAMED."""
    spans = [(max(s, t0), min(e, t1), s, e, n) for n, s, e in spans
             if min(e, t1) > max(s, t0)]
    cuts = sorted({t0, t1} | {x for a, b, *_ in spans for x in (a, b)})
    by_start = sorted(spans, key=lambda x: x[0])
    open_, nxt, segs = [], 0, []
    for a, b in zip(cuts, cuts[1:]):
        while nxt < len(by_start) and by_start[nxt][0] <= a:
            open_.append(by_start[nxt])
            nxt += 1
        open_ = [x for x in open_ if x[1] > a]
        name = (max(open_, key=lambda x: (x[2], -x[3]))[4] if open_ else UNNAMED)
        segs.append((a, b, name))
    return segs


def _idle(ex: dict, t0: int, t1: int) -> list[list[tuple[int, int]]]:
    """Per device plane that ran an operation in the window, its idle
    intervals (the window less the union of its operations)."""
    out = []
    for events in ex["ops"].values():
        busy = trace._union((a, b) for _, a, b in trace._clip(events, t0, t1))
        if not busy:
            continue
        edges = [t0] + [x for ab in busy for x in ab] + [t1]
        out.append([(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]])
    return out


def reduce(ex: dict) -> dict:
    """The spans of ``ex`` (:func:`bench.trace.extract` plus ``"spans"``)
    against its device operations, over the window:

    * ``span_s``, ``span_bytes``, ``span_n``: inclusive seconds (clipped to
      the window), bytes and count per span name, over every thread;
    * ``idle_by_span``: device idle seconds split by the innermost span
      open on an operation's thread; they sum to the window's idle time
      (averaged over device planes, as ``busy_s`` is);
    * ``idle_pct``: ``idle_by_span`` summed per layer (:data:`LAYERS`) and
      unattributed (``"-"``), in percent of the window;
    * ``lane_decode_share``: percent of ``gwlz.decode`` spent in
      ``gwlz.decode.lanes``, where the window decodes.

    Span names are matched on the part before ``#`` (annotation metadata)."""
    t0, t1 = trace.window_bounds(ex)
    spans = [(n.split("#", 1)[0], th, s, s + d, nb) for n, th, s, d, nb in ex["spans"]]
    span_s: dict[str, float] = defaultdict(float)
    span_bytes: dict[str, int] = defaultdict(int)
    span_n: dict[str, int] = defaultdict(int)
    for n, _, s, e, nb in spans:
        if min(e, t1) > max(s, t0):
            span_s[n] += (min(e, t1) - max(s, t0)) / 1e9
            span_bytes[n] += nb
            span_n[n] += 1
    threads = {th for n, th, *_ in spans if n in OPERATIONS}
    segs = _innermost([(n, s, e) for n, th, s, e, _ in spans if th in threads],
                      t0, t1)
    planes = _idle(ex, t0, t1)
    idle: dict[str, float] = defaultdict(float)
    for gaps in planes:
        i = 0
        for a, b in gaps:
            while i < len(segs) and segs[i][1] <= a:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < b:
                sa, sb, name = segs[j]
                idle[name] += (min(b, sb) - max(a, sa)) / 1e9 / len(planes)
                j += 1
    window_s = (t1 - t0) / 1e9
    pct = {layer: 100.0 * sum(v for k, v in idle.items() if k.startswith(p)) / window_s
           for layer, p in LAYERS.items()}
    pct[UNNAMED] = 100.0 * idle.get(UNNAMED, 0.0) / window_s
    out = {"window_s": window_s, "span_s": dict(span_s),
           "span_bytes": dict(span_bytes), "span_n": dict(span_n),
           "idle_by_span": dict(idle), "idle_pct": pct}
    if span_s.get("gwlz.decode", 0.0) > 0:
        out["lane_decode_share"] = (100.0 * span_s.get("gwlz.decode.lanes", 0.0)
                                    / span_s["gwlz.decode"])
    return out


def head(ex: dict, seconds: float) -> dict:
    """The first ``seconds`` of the window: every event that overlaps them
    (of the other host events, those of 1 ms or more, which name idle gaps
    of 2 ms or more), and the window annotation cut to them."""
    t0, _ = trace.window_bounds(ex)
    t1 = t0 + int(seconds * 1e9)

    def keep(events):
        return [list(e) for e in events if e[1] < t1 and e[1] + e[2] > t0]

    host = [e for e in keep(ex["host"]) if e[0] != trace.WINDOW and e[2] >= 10**6]
    return {"ops": {p: keep(v) for p, v in ex["ops"].items()},
            "modules": {p: keep(v) for p, v in ex["modules"].items()},
            "host": [[trace.WINDOW, t0, t1 - t0]] + host,
            "spans": [s for s in ex["spans"] if s[2] < t1 and s[2] + s[3] > t0]}


def run_traced(cell: str, seed: int, seconds: float, **kw) -> tuple[dict, dict]:
    """``harness.run_cell`` traced, with ``stages`` (:func:`reduce`) added to
    its result; also returns the extract, spans included."""
    from bench import harness

    # the harness reduces the trace and deletes it in one step, so the spans
    # are read from the same directory as it extracts
    seen = {}
    plain = trace.extract

    def extract(trace_dir):
        ex = plain(trace_dir)
        ex["spans"] = extract_spans(trace_dir)
        seen["ex"] = ex
        return ex

    trace.extract = extract
    try:
        result = harness.run_cell(cell, seed, seconds, True, **kw)
    finally:
        trace.extract = plain
    result["stages"] = reduce(seen["ex"])
    return result, seen["ex"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save-extract")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness, registry

    cell = registry.load_cell(args.workload)
    cache = harness.enable_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"stages: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    harness.log(f"devices {len(devs)} x {devs[0].device_kind}; compile cache {cache}")
    result, ex = run_traced(args.workload, args.seed, args.seconds,
                            start_age=harness.process_age_s())
    harness.log("idle_pct " + json.dumps(result["stages"]["idle_pct"]))
    if args.save_extract:
        Path(args.save_extract).write_text(
            json.dumps(head(ex, EXTRACT_SECONDS)))
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
