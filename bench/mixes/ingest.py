"""Back-to-back streamed ingest: ``api.compress_stream`` of the whole field,
from host memory into a container on local disk, with the enhancer trained
or not as the traffic file says (``"enhance"``).

Check: every container the window wrote decodes (``api.open``, full
decode) to the reference reconstruction of the field; containers that are
byte-identical to a checked one are covered by it.
"""
from __future__ import annotations

import os
import shutil

from bench import work
from bench.mixes import common


class Mix:
    def __init__(self, cell, seed: int, workdir, limits: dict):
        self.cell, self.seed, self.dir, self.limits = cell, seed, workdir, limits
        self.cfg = cell.config
        self.enhance = bool(cell.traffic["enhance"])
        self.ops: list[common.Op] = []

    def _ingest(self, path):
        rep = common.ingest(self.x, path, self.cfg, self.enhance)
        return rep, os.path.getsize(path)

    def setup(self) -> None:
        with common.phase("field"):
            self.x = common.host_field(self.cfg, self.seed)
        warm = self.dir / "warm.gwtc"
        with common.phase("warm_ingest"):
            self._ingest(warm)  # compiles and loads every program the window runs
        os.unlink(warm)

    def window(self, seconds: float) -> None:
        def op(i):
            path = self.dir / f"ingest-{i}.gwtc"
            rep, size = self._ingest(path)
            return self.x.nbytes, {"path": path, "size": size, "report": rep}

        self.ops = common.run_window(seconds, op)

    def end_to_end(self) -> dict:
        return {"ingest_MBps": common.rate_mb_s(self.ops),
                "ratio": sum(o.nbytes for o in self.ops)
                / sum(o.info["size"] for o in self.ops)}

    def counters(self) -> dict:
        reps = [o.info["report"] for o in self.ops]
        return {"kind": "ingest", "ops": len(self.ops),
                "op_seconds": sum(o.end - o.start for o in self.ops),
                "host_stage_s": sum(r.host_stage_s for r in reps),
                "programs_compiled": sum(r.programs_compiled for r in reps),
                "entropy_device": all(r.entropy_device for r in reps),
                "reservoir_tiles": reps[-1].reservoir_tiles,
                "container_bytes": sum(o.info["size"] for o in self.ops)}

    def work(self) -> dict:
        """Device work the window's ingests require, per kernel and whole."""
        n, vox = len(self.ops), self.x.size
        coded = sum(o.info["size"] for o in self.ops)
        out = {"lorenzo_quant": work.lorenzo_quant(n * vox),
               "huffman_pack": work.huffman_pack(n * vox, coded)}
        if self.enhance:
            e = self.cfg["enhancer"]
            t = int(self.cfg["tile"])
            slices = self.ops[-1].info["report"].reservoir_tiles * t
            steps = e["epochs"] * max(slices // e["batch_size"], 1)
            out["enhancer_training"] = tuple(
                n * v for v in work.enhancer_training(
                    steps, e["batch_size"] * t * t, 2 * slices * t * t,
                    e["channels"]))
        out["whole"] = work.add(*out.values())
        return out

    def check(self) -> tuple[dict, int, int]:
        """Decode each distinct container and compare it with the reference."""
        import numpy as np

        distinct: dict[str, object] = {}
        for o in self.ops:
            key = common.digest(np.fromfile(o.info["path"], np.uint8))
            distinct.setdefault(key, o.info["path"])
        worst = None
        for path in distinct.values():
            out = common.full_decode(path)
            nums = common.compare_with_reference(
                self.x, out, self.cfg, common.model_blob(path))
            del out
            if worst is None or nums["mismatch_share"] > worst["mismatch_share"]:
                worst = nums
        worst["containers_distinct"] = len(distinct)
        self.readings = worst
        return self._numbers(worst), len(self.ops), 0

    def _numbers(self, nums: dict) -> dict:
        lim = self.limits
        out = {"mismatch_share": {"value": nums["mismatch_share"],
                                  "max": lim["mismatch_share"]}}
        if self.enhance:
            out["enh_err"] = {"value": nums["enh_err"], "max": lim["enh_err"]}
            out["enh_gain_db"] = {"value": nums["psnr_db"] - nums["psnr_base_db"],
                                  "min": lim["enh_gain_db"]}
        else:
            out["over_bound_ulp"] = {"value": nums["over_bound_ulp"],
                                     "max": lim["over_bound_ulp"]}
        return out

    def control(self) -> dict:
        """Each control's compared numbers (``common.controls``) on the last
        container's model; with an enhancer, also the witness reading
        ``gap_eb_bf16_operands`` of that container's decode."""
        path = self.ops[-1].info["path"]
        blob = common.model_blob(path) if self.enhance else None
        out = {k: self._numbers(v)
               for k, v in common.controls(self.x, self.cfg, blob).items()}
        if blob is not None:
            self.readings["gap_eb_bf16_operands"] = common.operand_gap(
                self.x, common.full_decode(path), self.cfg, blob)
        return out

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
