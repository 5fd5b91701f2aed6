"""Shared arithmetic of the per-layer readers in ``bench/metrics/``.

Each reader gets the run's context: ``trace`` (``bench.trace.reduce`` of the
traced window), ``counters`` and ``work`` (from the mix) and ``device``.  A
reader returns ``None`` where it finds nothing to read, never a 0 that
stands for "not measured".
"""
from __future__ import annotations

from bench import trace, work

# Device operations of each kernel: its Pallas call's instruction, in
# whatever program holds it (op names are ``<program>/<instruction>``)
KERNELS = {
    "lorenzo_quant": ("/lorenzo_quant_tiles",),
    "huffman_pack": ("/huffman_encode_pack",),
    "huffman_probe": ("/huffman_decode_probe",),
}
# Runs of jitted programs, by function name (``bench.trace.program_name``)
PROGRAMS = {
    "enhancer_training": ("train_step", "_gate_groups", "_bn_calibrate"),
    "enhancer_inference": ("_enhance_tiles_mapped",),
}


def of_kind(ctx: dict, kind: str) -> bool:
    return ctx["counters"].get("kind") == kind and ctx["counters"].get("ops", 0) > 0


def kernel_roofline(ctx: dict, kind: str, kernel: str) -> float | None:
    """Least time the kernel's required work could take on this chip, over
    the device time its operations took in the window, in percent."""
    if not of_kind(ctx, kind) or kernel not in ctx["work"]:
        return None
    spent = trace.seconds_matching(ctx["trace"]["op_s"], KERNELS[kernel])
    if spent <= 0:
        return None
    peak = work.peaks(ctx["device"]["kind"])
    return 100.0 * work.least_seconds(*ctx["work"][kernel], peak) / spent


def program_seconds_per_op(ctx: dict, kind: str, program: str) -> float | None:
    """Device seconds of the program's runs in the window, per operation."""
    if not of_kind(ctx, kind):
        return None
    spent = sum(v for k, v in ctx["trace"]["module_s"].items()
                if k in PROGRAMS[program])
    if spent <= 0:
        return None
    return spent / ctx["counters"]["ops"]


def idle_share(ctx: dict, kind: str) -> float | None:
    if not of_kind(ctx, kind):
        return None
    t = ctx["trace"]
    if t["window_s"] <= 0 or t["devices"] == 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def mfu(ctx: dict, kind: str) -> float | None:
    """The least time the chip could take for the window's required work,
    over the traced window, in percent."""
    if not of_kind(ctx, kind) or "whole" not in ctx["work"] \
            or ctx["trace"]["devices"] == 0:
        return None
    peak = work.peaks(ctx["device"]["kind"])
    return 100.0 * work.least_seconds(*ctx["work"]["whole"], peak) / ctx["trace"]["window_s"]
