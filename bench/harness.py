"""One run of one cell: set-up, the measured window, the check, the result.

``run_cell`` is everything ``run.py`` does except finding the chip, so the
tests can drive a whole run on the CPU at a tiny size.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from pathlib import Path

from bench import registry, trace
from bench.mixes import common

# set before anything compiles: every program lands in the persistent cache,
# so a second run in a checkout compiles nothing
_CACHE_MIN_SECONDS = 0.0

_COMPILE_EVENTS = {"/jax/core/compile/backend_compile_duration": "compiled",
                   "/jax/compilation_cache/cache_retrieval_time_sec": "loaded"}


def process_age_s() -> float:
    """Seconds since this process was created (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


class CompileCounter:
    """Programs compiled, and loaded from the persistent cache, per phase
    (``"setup"`` until the window opens, then ``"window"``), with seconds."""

    def __init__(self):
        import jax

        self.phase = "setup"
        self.seen: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, secs: float, **_kw) -> None:
        kind = _COMPILE_EVENTS.get(event)
        if kind is not None:
            n, s = self.seen.get((self.phase, kind), (0, 0.0))
            self.seen[(self.phase, kind)] = (n + 1, s + secs)

    def count(self, phase: str) -> int:
        return sum(self.seen.get((phase, k), (0, 0.0))[0]
                   for k in ("compiled", "loaded"))

    def line(self, phase: str) -> str:
        return " ".join(f"{k} {n} in {s:.1f} s" for k in ("compiled", "loaded")
                        for n, s in [self.seen.get((phase, k), (0, 0.0))])


def enable_cache() -> str:
    """Turn on the persistent compile cache at its one fixed path inside the
    checkout (``repro.compile_cache``'s default), whatever the environment
    names, so that runs in another checkout share nothing with this one."""
    import jax

    from repro.compile_cache import enable_compile_cache

    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      _CACHE_MIN_SECONDS)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def log(*parts) -> None:
    print(*parts, flush=True)


def _device() -> dict:
    """The devices as JAX reports them, with the peak on the fullest chip."""
    import jax

    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs]
    if not stats[0]:
        log("memory_stats: not reported by this backend")
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def _per_layer(cell: registry.Cell, ctx: dict, bench_dir: Path) -> dict:
    out = {}
    for m in cell.per_layer:
        value = registry.metric_reader(m["name"], bench_dir)(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, traced: bool, *,
             root: Path = registry.ROOT, bench_dir: Path = registry.BENCH,
             start_age: float = 0.0, control: bool = False) -> dict:
    """Run cell ``name`` once and return its result object.

    ``start_age`` is how long the process had run before this call's clock
    started (set-up counts from process creation).  ``control`` adds the compared
    numbers of each lower-precision control (``bench/control.py``) under
    ``"control"``, by name; the benchmark's own runs never compute them."""
    import jax

    t_start = time.perf_counter() - start_age
    cell = registry.load_cell(name, root, bench_dir)
    mix_cls = registry.mix_module(cell.traffic["kind"]).Mix
    counter = CompileCounter()
    workdir = Path(tempfile.mkdtemp(prefix="bench-"))
    mix = mix_cls(cell, seed, workdir, common.limits(cell, bench_dir))
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if traced else None
    summary = None
    try:
        mix.setup()
        log(f"setup_programs {counter.line('setup')}; set-up so far "
            f"{time.perf_counter() - t_start:.1f} s")
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        counter.phase = "window"
        setup_s = time.perf_counter() - t_start
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            mix.window(seconds)
        counter.phase = "after"
        if traced:
            jax.profiler.stop_trace()
        device = _device()
        log(f"compiles_in_window {counter.count('window')} "
            f"({counter.line('window')})")
        log(f"peak_bytes_in_use {device['memory_peak_bytes']}")
        counters = mix.counters()
        log("counters " + json.dumps({k: v for k, v in counters.items()
                                      if not isinstance(v, list)}))
        _log_entropy_paths()
        if traced:
            t_read = time.perf_counter()
            summary = trace.reduce(trace.extract(str(trace_dir)))
            device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
            log("trace " + json.dumps({k: summary[k] for k in
                                       ("window_s", "busy_s", "devices")})
                + f" read in {time.perf_counter() - t_read:.1f} s")
        e2e = mix.end_to_end()
        e2e["setup_s"] = setup_s
        required = mix.work()
        checks, attempted, failed = mix.check()
        control_checks = mix.control() if control else None
        log("readings " + json.dumps(mix.readings))
    finally:
        mix.close()
        if trace_dir is not None:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
    correct = all(_within(c) for c in checks.values())
    if traced:
        ctx = {"trace": summary, "counters": counters, "work": required,
               "device": device}
        metrics = _per_layer(cell, ctx, bench_dir)
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    if control:
        result["control"] = {
            name: {"checks": c, "correct": all(_within(v) for v in c.values())}
            for name, c in control_checks.items()}
    result["checks"] = checks
    return result


def _within(check: dict) -> bool:
    v = check["value"]
    if "max" in check and not v <= check["max"]:
        return False
    if "min" in check and not v >= check["min"]:
        return False
    return True


def _log_entropy_paths() -> None:
    from repro.sz import entropy, tiled

    log("entropy_lanes " + json.dumps(entropy.entropy_path_stats()))
    d = tiled.dispatch_stats()
    log(f"decode_programs {d['programs']} dispatches {d['dispatches']}")


def print_result(result: dict) -> None:
    """Compared numbers last on stderr, then the result as stdout's last line."""
    for k, c in result["checks"].items():
        lim = " ".join(f"{b} {c[b]!r}" for b in ("max", "min") if b in c)
        print(f"check {k} {c['value']!r} {lim}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
