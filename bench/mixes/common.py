"""What the kinds of mix share: the field, the program's entry points
as a user calls them, and the comparison with the reference."""
from __future__ import annotations

import hashlib
import json
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import fields, reference
from bench.registry import BENCH, Cell


@dataclass
class Op:
    """One whole operation of the window."""

    start: float
    end: float
    nbytes: int  # input bytes (ingest) or bytes reconstructed (decode)
    info: dict = field(default_factory=dict)


@contextmanager
def phase(label: str):
    """Log how long a step of set-up took (``setup_phase <label> <s>``)."""
    t = time.perf_counter()
    yield
    print(f"setup_phase {label} {time.perf_counter() - t:.1f} s", flush=True)


def limits(cell: Cell, bench_dir: Path = BENCH) -> dict:
    """``bench/limits/<cell>.json``: each compared number's limit."""
    return json.loads((Path(bench_dir) / "limits" / f"{cell.name}.json").read_text())


def host_field(cfg: dict, seed: int) -> np.ndarray:
    """The configuration's field for ``seed``, made on the device in one
    call and copied to host memory, where a simulation hands it over."""
    x = fields.make_field(cfg["field"], int(cfg["side"]), int(cfg["field_seed"]),
                          seed, int(cfg["tile"]))
    host = np.asarray(x)
    del x
    return host


def abs_eb(cfg: dict) -> float:
    """The configuration's absolute bound: ``rel_eb`` times the field's
    published value range."""
    lo, hi = cfg["value_range"]
    return float(cfg["rel_eb"]) * (float(hi) - float(lo))


def train_config(cfg: dict):
    from repro.core.trainer import GWLZTrainConfig

    e = cfg["enhancer"]
    return GWLZTrainConfig(
        n_groups=e["n_groups"], channels=e["channels"], epochs=e["epochs"],
        batch_size=e["batch_size"], lr=e["lr"], seed=e["seed"])


def ingest(x: np.ndarray, path: Path, cfg: dict, enhance: bool):
    """One ``api.compress_stream`` of the whole field into ``path``."""
    from repro import api

    t = int(cfg["tile"])
    return api.compress_stream(
        x, str(path), abs_eb=abs_eb(cfg), tile=(t, t, t),
        predictor=cfg["predictor"], backend=cfg["backend"],
        mem_budget=int(cfg["mem_budget"]),
        enhance=train_config(cfg) if enhance else False)


def full_decode(path: Path) -> np.ndarray:
    """``np.asarray(api.open(path))``: the whole volume, enhancer applied."""
    from repro import api

    with api.open(str(path)) as vol:
        return np.asarray(vol)


def model_blob(path: Path) -> bytes | None:
    """The container's enhancer record, or None for a plain container."""
    from repro import api

    with api.open(str(path)) as vol:
        blob = vol.artifact.extras.get("gwlz")
    return None if blob is None else bytes(blob)


def digest(a: np.ndarray) -> str:
    return hashlib.sha1(np.ascontiguousarray(a).tobytes()).hexdigest()


def compare_with_reference(x: np.ndarray, out: np.ndarray, cfg: dict,
                           blob: bytes | None) -> dict:
    """The program's (or a control's) reconstruction ``out`` against the
    reference built from the field ``x``; with an enhancer, also how far its
    output departs from the reference enhancer's (``enh_err``); PSNRs in dB."""
    import jax.numpy as jnp

    eb = abs_eb(cfg)
    tile = int(cfg["tile"])
    xd = jnp.asarray(x)
    base = reference.base_recon(xd, eb)
    if blob is None:
        ref = base
    else:
        ref = reference.enhance(base, tile, reference.parse_model(blob))
    nums = reference.compare(out, ref, xd, eb)
    if blob is not None:
        nums["enh_err"] = reference.enhancer_error(out, ref, base, tile)
    nums["psnr_db"] = reference.psnr(xd, out)
    nums["psnr_base_db"] = reference.psnr(xd, base)
    nums["eb"] = eb
    del ref, base, xd
    return nums


def controls(x: np.ndarray, cfg: dict, blob: bytes | None) -> dict:
    """The reference one precision lower than the configuration states, in
    the program's place.  ``"bf16"``: all of it in bfloat16 (the
    configuration states float32).  With an enhancer, ``"fp8_enhancer"``:
    the base reconstruction in float32 and the enhancer's conv operands in
    float8 e4m3 (the configuration states one bfloat16 pass for them).
    Each maps to its readings by ``compare_with_reference``."""
    import jax.numpy as jnp

    eb = abs_eb(cfg)
    tile = int(cfg["tile"])
    model = None if blob is None else reference.parse_model(blob)
    xd = jnp.asarray(x)
    outs = {"bf16": reference.reconstruct(xd, eb, tile, model, dtype="bfloat16")}
    if model is not None:
        outs["fp8_enhancer"] = reference.enhance(
            reference.base_recon(xd, eb), tile, model, operands="float8_e4m3")
    del xd
    return {k: compare_with_reference(x, np.asarray(v), cfg, blob)
            for k, v in outs.items()}


def operand_gap(x: np.ndarray, out: np.ndarray, cfg: dict, blob: bytes) -> float:
    """Widest gap, in eb, between ``out`` and the reference enhancer run
    with its conv operands rounded to bfloat16, as the TPU's default matmul
    precision rounds them (a witness, not a compared number)."""
    import jax.numpy as jnp

    eb = abs_eb(cfg)
    ref = reference.enhance(reference.base_recon(jnp.asarray(x), eb), int(cfg["tile"]),
                            reference.parse_model(blob), operands="bfloat16")
    return float(jnp.max(jnp.abs(jnp.asarray(out) - ref)) / eb)


def run_window(seconds: float, op) -> list[Op]:
    """Whole operations back to back until ``seconds`` have passed; the one
    that started last runs to its end."""
    ops: list[Op] = []
    t0 = time.perf_counter()
    while not ops or time.perf_counter() - t0 < seconds:
        s = time.perf_counter()
        nbytes, info = op(len(ops))
        ops.append(Op(s, time.perf_counter(), nbytes, info))
    return ops


def rate_mb_s(ops: list[Op]) -> float:
    span = ops[-1].end - ops[0].start
    return sum(o.nbytes for o in ops) / 1e6 / span
