"""The work each kernel and each whole operation requires, from shapes.

Counted from what the algorithm needs, not from how the program does it
today, so a later change that removes waste is read against the same
work.  Every function returns ``(flops, bytes)`` of device work; the
least time the chip could take is the larger of ``flops / peak FLOP/s``
and ``bytes / peak bytes/s`` (:func:`least_seconds`).
"""
from __future__ import annotations

import json
from pathlib import Path

_PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown device is an error."""
    table = json.loads(_PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in {_PEAKS.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def lorenzo_quant(voxels: int) -> tuple[float, float]:
    """Prequantize + 3D Lorenzo: read f32 values, write int32 codes; one
    divide and three differences per voxel."""
    return 4.0 * voxels, 8.0 * voxels


def huffman_pack(voxels: int, coded_bytes: int) -> tuple[float, float]:
    """Read int32 codes, write the packed code words (integer work only)."""
    return 0.0, 4.0 * voxels + coded_bytes


def huffman_probe(voxels: int, coded_bytes: int) -> tuple[float, float]:
    """Read the packed code words, write int32 codes."""
    return 0.0, coded_bytes + 4.0 * voxels


def lorenzo_decode(voxels: int) -> tuple[float, float]:
    """Codes back to values: three prefix sums and a scale per voxel."""
    return 4.0 * voxels, 8.0 * voxels


def enhancer_forward(voxels: int, channels: int = 9) -> tuple[float, float]:
    """One group's CNN per voxel (paper Fig. 3): 3x3 conv 1->C and C->1
    (2 FLOPs per multiply-add), BatchNorm and ReLU on C channels; read the
    base value and write the enhanced one.  One group per voxel, not G."""
    flops = voxels * (2 * 9 * channels + 2 * 9 * channels + 4 * channels)
    return float(flops), 8.0 * voxels


def enhancer_training(steps: int, pixels_per_step: int, passes_pixels: int,
                      channels: int = 9) -> tuple[float, float]:
    """Training: forward + backward (3x the forward) per step's pixels, and
    the forward passes over the training set that calibrate and gate."""
    f_step, b_step = enhancer_forward(steps * pixels_per_step, channels)
    f_pass, b_pass = enhancer_forward(passes_pixels, channels)
    return 3.0 * f_step + f_pass, 3.0 * b_step + b_pass


def add(*parts: tuple[float, float]) -> tuple[float, float]:
    return sum(p[0] for p in parts), sum(p[1] for p in parts)
