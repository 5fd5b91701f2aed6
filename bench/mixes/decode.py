"""Back-to-back full decodes, ``np.asarray(api.open(path))``, of one
container that set-up wrote from the field (enhanced or not as the
traffic file says).

Check: every decode the window produced is compared with the reference
reconstruction of the field; decodes byte-identical to a checked one are
covered by it.
"""
from __future__ import annotations

import shutil

from bench import work
from bench.mixes import common


class Mix:
    def __init__(self, cell, seed: int, workdir, limits: dict):
        self.cell, self.seed, self.dir, self.limits = cell, seed, workdir, limits
        self.cfg = cell.config
        self.enhance = bool(cell.traffic["enhance"])
        self.ops: list[common.Op] = []
        self.outs, self.last_out = [], None

    def setup(self) -> None:
        with common.phase("field"):
            self.x = common.host_field(self.cfg, self.seed)
        self.path = self.dir / "field.gwtc"
        with common.phase("ingest"):
            common.ingest(self.x, self.path, self.cfg, self.enhance)
        self.blob = common.model_blob(self.path)
        self.size = self.path.stat().st_size
        with common.phase("warm_decode"):
            common.full_decode(self.path)  # compiles and loads the decode programs

    def window(self, seconds: float) -> None:
        def op(_i):
            out = common.full_decode(self.path)
            self.outs.append(out)
            return out.nbytes, {}

        self.ops = common.run_window(seconds, op)

    def end_to_end(self) -> dict:
        return {"decode_MBps": common.rate_mb_s(self.ops)}

    def counters(self) -> dict:
        return {"kind": "decode", "ops": len(self.ops),
                "op_seconds": sum(o.end - o.start for o in self.ops),
                "container_bytes": len(self.ops) * self.size}

    def work(self) -> dict:
        n, vox = len(self.ops), self.x.size
        coded = n * self.size
        out = {"huffman_probe": work.huffman_probe(n * vox, coded),
               "lorenzo_decode": work.lorenzo_decode(n * vox)}
        if self.enhance:
            out["enhancer_forward"] = work.enhancer_forward(
                n * vox, self.cfg["enhancer"]["channels"])
        out["whole"] = work.add(*out.values())
        return out

    def check(self) -> tuple[dict, int, int]:
        distinct = {}
        for out in self.outs:
            distinct.setdefault(common.digest(out), out)
        self.last_out = self.outs[-1]
        self.outs = []
        worst = None
        for out in distinct.values():
            nums = common.compare_with_reference(self.x, out, self.cfg, self.blob)
            if worst is None or nums["mismatch_share"] > worst["mismatch_share"]:
                worst = nums
        worst["decodes_distinct"] = len(distinct)
        self.readings = worst
        return self._numbers(worst), len(self.ops), 0

    def _numbers(self, nums: dict) -> dict:
        out = {"mismatch_share": {"value": nums["mismatch_share"],
                                  "max": self.limits["mismatch_share"]}}
        if self.enhance:
            out["enh_err"] = {"value": nums["enh_err"],
                              "max": self.limits["enh_err"]}
        else:
            out["over_bound_ulp"] = {"value": nums["over_bound_ulp"],
                                     "max": self.limits["over_bound_ulp"]}
        return out

    def control(self) -> dict:
        """Each control's compared numbers (``common.controls``); with an
        enhancer, also the witness reading ``gap_eb_bf16_operands`` of the
        window's last decode."""
        out = {k: self._numbers(v)
               for k, v in common.controls(self.x, self.cfg, self.blob).items()}
        if self.blob is not None:
            self.readings["gap_eb_bf16_operands"] = common.operand_gap(
                self.x, self.last_out, self.cfg, self.blob)
        return out

    def close(self) -> None:
        self.outs, self.last_out = [], None
        shutil.rmtree(self.dir, ignore_errors=True)
