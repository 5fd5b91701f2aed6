"""Enhanced ingest over every chip of the host: the ``ingest`` mix, whose
``api.compress_stream`` fans the tile batches and the enhancer's group
models over all the host's devices (the tile mesh).

Counters add enhancer training's program spans, summed over the window's
``StreamReport.stages``.  Check, on one device: the ``ingest`` mix's check
(the containers read back and held to the reference), then one ingest of
the same field, whose container must be byte-identical to the window's last
one (``mesh_bytes_differ``).  The read-back runs on one device so that the
check judges what the mesh ingest wrote, on any program that can write it;
decoding over the mesh has tests of its own."""
from __future__ import annotations

import numpy as np

from bench.mixes import ingest


class Mix(ingest.Mix):
    def _stage(self, name: str, field: int):
        """Total of one field of span ``name`` over the window's ingests
        (0 count, 1 seconds, 2 bytes); None where no ingest recorded it."""
        got = [o.info["report"].stages[name] for o in self.ops
               if name in o.info["report"].stages]
        return sum(g[field] for g in got) if got else None

    def counters(self) -> dict:
        out = super().counters()
        mesh = [s for s in (self._stage("gwlz.train.shard", 1),
                            self._stage("gwlz.train.gather", 1)) if s is not None]
        out.update(train_s=self._stage("gwlz.train", 1),
                   train_step_s=self._stage("gwlz.train.step", 1),
                   train_steps=self._stage("gwlz.train.step", 0),
                   train_mesh_s=sum(mesh) if mesh else None,
                   group_mesh_bytes=self._stage("gwlz.train.group_mesh", 2))
        return out

    def check(self) -> tuple[dict, int, int]:
        import jax

        from repro.launch.sharding import pin_tile_devices

        one = self.dir / "one-device.gwtc"
        with pin_tile_devices(jax.devices()[:1]):
            numbers, attempted, failed = super().check()
            self._ingest(one)
        a = np.fromfile(self.ops[-1].info["path"], np.uint8)
        b = np.fromfile(one, np.uint8)
        n = min(a.size, b.size)
        differ = int(np.count_nonzero(a[:n] != b[:n])) + abs(a.size - b.size)
        self.readings["mesh_bytes_differ"] = differ
        numbers["mesh_bytes_differ"] = {"value": differ,
                                        "max": self.limits["mesh_bytes_differ"]}
        return numbers, attempted, failed
