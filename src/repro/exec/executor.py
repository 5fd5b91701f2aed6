"""Bounded-memory streaming compression executor.

The volume never materializes: the plan's contiguous tile-id runs are
pulled from a :class:`~repro.exec.sources.TileSource` one batch at a time,
each batch runs the device transform (prequant + predict, fanned across
the mesh by the predictor's ``encode_tiles``), and the host entropy stage
(lane serialization + container append) runs on a single background worker
so host coding of batch *k* overlaps device work on batch *k+1*.  In-flight
work is capped at one encoded batch, so at most two batches of working set
are alive — the plan sizes batches at half the byte budget, keeping the
tracked peak within it.

``MemTracker`` is the RSS hook the acceptance test asserts against: it
accounts the executor-owned buffers exactly (batch input, payload leaves,
reservoir), where process-level ``ru_maxrss`` is polluted by allocator and
JIT baselines.  Both land in the :class:`StreamReport`.

Fault tolerance (docs/ROBUSTNESS.md): the device encode and the host
append both run under a :class:`~repro.runtime.fault.RetryPolicy` — a
transient ``RuntimeError``/``OSError`` is retried with backoff instead of
killing the stream (``injector``/``write_injector`` hooks let tests drive
deterministic fault schedules through the real code paths).  Each batch's
lanes are journaled by :meth:`GWTCWriter.commit` once appended, so an
exhausted retry leaves a *resumable* partial container behind
(``resume=True`` picks up from the first uncommitted batch) rather than
unlinking the work done so far.
"""
from __future__ import annotations

import contextvars
import os
import resource
import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

from repro import obs
from repro.exec.plan import StreamPlan, plan_stream
from repro.exec.sources import TileSource, as_source, value_range
from repro.exec.writer import GWTCWriter
from repro.runtime.fault import RetryPolicy


class MemTracker:
    """Byte accounting for executor-owned buffers (current + high-water)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.current = 0
        self.peak = 0

    def add(self, n: int) -> None:
        with self._lock:
            self.current += int(n)
            self.peak = max(self.peak, self.current)

    def sub(self, n: int) -> None:
        with self._lock:
            self.current -= int(n)


@dataclass
class StreamReport:
    """What a finished streaming compression did and what it cost."""

    path: str | None
    shape: tuple[int, ...]
    tile: tuple[int, ...]
    n_tiles: int
    n_batches: int
    batch_tiles: int
    nbytes: int
    eb_abs: float
    predictor: str
    backend: str
    mem_budget: int
    peak_tracked_bytes: int
    ru_maxrss_kb: int
    enhanced: bool = False
    reservoir_tiles: int = 0
    # fault-tolerance accounting: total retried attempts, the batch indices
    # that needed at least one retry, and how many batches a resume skipped
    retries: int = 0
    failed_batches: tuple[int, ...] = field(default_factory=tuple)
    resumed_batches: int = 0
    # entropy-stage accounting: whether lane packing ran in the device stage
    # (Pallas Huffman kernels) and the writer thread's wall time (the
    # ``gwlz.ingest.append`` span) — with device entropy that is container
    # append+commit only, and lane coding runs on the caller's thread
    host_stage_s: float = 0.0
    entropy_device: bool = False
    # backend compiles this stream ran (0 = every program was already
    # loaded in this process)
    programs_compiled: int = 0
    # (count, seconds, bytes) per span name (``repro.obs``) over the stream,
    # from the caller's thread and the writer thread
    stages: dict = field(default_factory=dict)

    @property
    def peak_over_budget(self) -> float:
        return self.peak_tracked_bytes / max(self.mem_budget, 1)


def _resolve_eb_streaming(source: TileSource, rel_eb, abs_eb) -> float:
    """Streaming mirror of ``repro.sz.quantizer.resolve_eb``: same f32
    range arithmetic (so streamed and eager artifacts agree on eb bit-for-
    bit), fed by a block prepass instead of a whole-volume reduction."""
    if (rel_eb is None) == (abs_eb is None):
        raise ValueError("pass exactly one of rel_eb / abs_eb")
    if rel_eb is not None:
        lo, hi = value_range(source)
        vrange = float(np.float32(hi) - np.float32(lo))
        abs_eb = rel_eb * max(vrange, float(np.finfo(np.float32).tiny))
        absmax = max(abs(lo), abs(hi))
        max_q = absmax / (2.0 * float(abs_eb))
        if max_q >= 2**30:
            raise ValueError(
                f"eb={abs_eb:g} too small for data magnitude "
                f"(q={max_q:.3g} >= 2^30)")
    return float(abs_eb)


def _tile_bounds(i: int, grid, tile, shape):
    coord = np.unravel_index(i, grid)
    lo = tuple(int(c) * t for c, t in zip(coord, tile))
    hi = tuple(min(l + t, d) for l, t, d in zip(lo, tile, shape))
    return lo, hi


def _read_batch(source: TileSource, ids, plan: StreamPlan) -> np.ndarray:
    """[B, *tile] float32 batch, padded to the plan's uniform width by
    repeating the final tile (so the device program compiles once)."""
    B = plan.batch_tiles
    out = np.empty((B,) + plan.tile, np.float32)
    for j, i in enumerate(ids):
        lo, hi = _tile_bounds(i, plan.grid, plan.tile, plan.shape)
        out[j] = source.read_tile(lo, hi, plan.tile)
    for j in range(len(ids), B):
        out[j] = out[len(ids) - 1]
    return out


def stream_compress(
    source,
    dest,
    *,
    tile=(64, 64, 64),
    rel_eb: float | None = None,
    abs_eb: float | None = None,
    backend: str = "huffman+zlib",
    predictor: str = "lorenzo",
    order: str = "cubic",
    max_levels: int = 5,
    mem_budget: int = 256 << 20,
    enhance=None,
    reservoir_tiles: int | None = None,
    shape=None,
    use_pallas: bool | None = None,
    retry: RetryPolicy | None = None,
    resume: bool = False,
    injector=None,
    write_injector=None,
) -> StreamReport:
    """Compress a streamed volume into a ``GWTC`` v3 container.

    ``source`` is anything :func:`repro.exec.sources.as_source` accepts;
    ``dest`` a path, writable file object, or an already-open
    :class:`GWTCWriter` (e.g. from ``GWDSWriter.stream_field``).  ``enhance``
    optionally trains group-wise GWLZ enhancers on a reservoir sample of
    (recon, residual) tile pairs — the bounded-memory stand-in for the
    eager path's whole-volume training set — and attaches the model before
    the footer is written.  Returns a :class:`StreamReport`; open the
    artifact with ``api.open`` (lazily — only decoded lanes are read).

    ``retry`` (default :class:`RetryPolicy()`) governs both the device
    encode and the host append; ``resume=True`` re-opens an interrupted
    path destination at its journaled commit point and streams only the
    uncommitted batches (Lorenzo resume is byte-identical to an
    uninterrupted run).  ``injector`` / ``write_injector`` are
    :class:`~repro.runtime.fault.FailureInjector` hooks for tests: the
    first fires per batch index inside the device encode, the second per
    global lane id inside the host append."""
    import jax

    from repro.sz.predictor import get_predictor
    from repro.sz.tiled import normalize_tile

    from repro.sz.entropy import _accel_default

    with obs.collect() as col, obs.span("gwlz.ingest"):
        retry = retry if retry is not None else RetryPolicy()
        src = as_source(source, shape=shape)
        tile = normalize_tile(tile, len(src.shape))
        eb = _resolve_eb_streaming(src, rel_eb, abs_eb)
        pred = get_predictor(predictor)
        levels = pred.plan(tile, max_levels)
        # device entropy moves lane packing into the device stage, so the host
        # stage shrinks to container append + commit (same auto-detect rule as
        # the entropy layer; bytes are bit-identical either way)
        device_entropy = _accel_default() if use_pallas is None else bool(use_pallas)
        plan = plan_stream(src.shape, tile, mem_budget, predictor=predictor,
                           levels=levels, device_entropy=device_entropy)
        want = (plan.shape, plan.tile, eb, backend, predictor, order, levels)

        start_tile, resumed_batches = 0, 0
        if resume:
            if enhance:
                raise ValueError(
                    "resume=True cannot train enhancers: the reservoir would "
                    "sample only the re-streamed batches, so the attached model "
                    "(and the container bytes) would depend on where the "
                    "interruption fell — re-run without resume to enhance")
            if isinstance(dest, GWTCWriter) or hasattr(dest, "write"):
                raise ValueError("resume=True needs a path destination "
                                 "(the commit journal lives next to the file)")
            writer, path = GWTCWriter.resume(dest), str(dest)
            aligned = plan.resume_point(writer.committed_lanes)
            if aligned != writer.committed_lanes:
                writer.truncate_lanes(aligned)  # mid-batch commit: redo the batch
            start_tile = aligned
            resumed_batches = start_tile // plan.batch_tiles
        elif isinstance(dest, GWTCWriter):
            # a pre-made writer already wrote its header; every header field must
            # agree with how the lanes will actually be encoded, or the container
            # would self-describe a decode that does not match its bytes
            writer, path = dest, None
        else:
            path = None if hasattr(dest, "write") else str(dest)
            writer = GWTCWriter(dest, shape=plan.shape, tile=plan.tile, eb_abs=eb,
                                backend=backend, predictor=predictor, order=order,
                                levels=levels)
        if resume or isinstance(dest, GWTCWriter):
            wrote = (writer.shape, writer.tile, writer.eb_abs, writer.backend,
                     writer.predictor, writer.order, writer.levels)
            if wrote != want:
                if resume:
                    writer.abort()
                raise ValueError(
                    f"writer header {wrote} does not match the encode settings "
                    f"{want} (shape, tile, eb_abs, backend, predictor, order, "
                    "levels must agree)")

        reservoir = None
        if enhance:
            from repro.core.trainer import GWLZTrainConfig, TileReservoir

            cfg = enhance if isinstance(enhance, GWLZTrainConfig) else GWLZTrainConfig()
            if reservoir_tiles is None:
                pair_bytes = 8 * int(np.prod(tile))  # f32 recon + f32 residual
                reservoir_tiles = max(4, (mem_budget // 4) // pair_bytes)
            reservoir = TileReservoir(int(reservoir_tiles), seed=cfg.seed)

        mem = MemTracker()
        pool = ThreadPoolExecutor(1, thread_name_prefix="gwtc-host")
        pending = None
        # retry accounting, shared between the main thread (device stage) and
        # the host worker — on_retry callbacks from both land here
        fault_lock = threading.Lock()
        retries = 0
        failed_batches: set[int] = set()

        def note_retry(bidx: int):
            def cb(_exc, _attempt):
                nonlocal retries
                with fault_lock:
                    retries += 1
                    failed_batches.add(bidx)
            return cb

        def host_stage(payload_np, ids, bidx: int, nbytes_held: int,
                       blobs=None) -> None:
            """``blobs`` set means the device stage already packed the lanes —
            the host stage is pure container append + commit."""
            def append_batch():
                if writer.can_rollback:
                    # drop any half-appended lanes from a previous attempt so
                    # the retry replays the whole batch from the commit point
                    writer.rollback_uncommitted()
                for j in range(len(ids)):
                    if write_injector is not None:
                        write_injector.maybe_fail(ids[j])
                    writer.append_lane(
                        blobs[j] if blobs is not None
                        else pred.lane_bytes(payload_np, j, backend))
                writer.commit()

            try:
                with obs.span("gwlz.ingest.append"):
                    if writer.can_rollback:
                        retry.run(append_batch, on_retry=note_retry(bidx))
                    else:
                        append_batch()  # shared sink: no safe replay, fail fast
            finally:
                mem.sub(nbytes_held)

        try:
            for bidx, run in enumerate(plan.batches(start_tile),
                                       start=resumed_batches):
                ids = list(run)
                with obs.span("gwlz.ingest.read"):
                    # the batch read stays OUTSIDE the retry scope: sources are
                    # forward-only streams, a re-read is not generally possible
                    batch = _read_batch(src, ids, plan)
                    # same f32-overflow guard as quantizer.resolve_eb, applied
                    # to the data actually seen (an abs_eb stream takes no
                    # range prepass)
                    max_q = float(np.abs(batch[: len(ids)]).max()) / (2.0 * eb)
                if max_q >= 2**30:
                    raise ValueError(
                        f"eb={eb:g} too small for data magnitude "
                        f"(q={max_q:.3g} >= 2^30)")
                mem.add(batch.nbytes)

                def encode():
                    if injector is not None:
                        injector.maybe_fail(bidx)
                    return pred.encode_tiles(batch, eb, order=order,
                                             levels=levels, use_pallas=use_pallas)

                with obs.span("gwlz.ingest.encode"):
                    payload, recon = retry.run(encode, on_retry=note_retry(bidx))
                with obs.span("gwlz.ingest.fetch", sum(
                        leaf.nbytes for leaf in jax.tree.leaves(payload))):
                    payload_np = jax.tree.map(np.asarray, payload)
                held = sum(leaf.nbytes for leaf in jax.tree.leaves(payload_np))
                blobs = None
                if device_entropy:
                    # device stage emits the packed lane bytes directly (Pallas
                    # encode kernel); only the lanes actually written, not the
                    # batch's repeat padding
                    with obs.span("gwlz.ingest.lanes"):
                        blobs = pred.lane_bytes_batch(payload_np, len(ids),
                                                      backend, use_pallas=True)
                    held += sum(len(b) for b in blobs)
                mem.add(held)
                if reservoir is not None:
                    with obs.span("gwlz.ingest.reservoir"):
                        recon_np = np.asarray(recon)[: len(ids)]
                        mem.add(recon_np.nbytes)
                        grew = reservoir.offer(recon_np,
                                               batch[: len(ids)] - recon_np)
                    mem.add(grew)
                    mem.sub(recon_np.nbytes)
                del recon
                mem.sub(batch.nbytes)
                del batch
                if pending is not None:
                    with obs.span("gwlz.ingest.wait_writer"):
                        pending.result()  # cap in-flight host work at one batch
                # the writer thread runs in a copy of this context, so its
                # spans reach this stream's collector
                pending = pool.submit(contextvars.copy_context().run, host_stage,
                                      payload_np, ids, bidx, held, blobs)
                del payload, payload_np, blobs
            if pending is not None:
                with obs.span("gwlz.ingest.wait_writer"):
                    pending.result()
                pending = None

            enhanced = False
            if reservoir is not None and len(reservoir):
                from repro.core.pipeline import serialize_model
                from repro.core.trainer import train_enhancers_streamed

                model, _hist = train_enhancers_streamed(reservoir, cfg)
                with obs.span("gwlz.train.serialize"):
                    writer.extras["gwlz"] = serialize_model(model)
                enhanced = True
            with obs.span("gwlz.ingest.finalize"):
                nbytes = writer.finalize()
        except BaseException:
            if pending is not None:  # drain the worker before touching the sink
                try:
                    pending.result()
                # the worker can only fail the ways the append path fails; a
                # propagating exception here would mask the original error
                except (OSError, RuntimeError, ValueError):
                    pass
                pending = None
            if not isinstance(dest, GWTCWriter):
                journaled = writer._journal_path is not None
                writer.abort()  # close the fd; no footer = detectably truncated
                if path is not None and not journaled:
                    try:
                        os.unlink(path)  # don't leave a garbage container behind
                    except OSError:
                        pass
                # journaled path dests keep the partial container + journal on
                # disk: that pair is exactly what resume=True needs
            raise
        finally:
            if pending is not None:  # a failed batch: drain the worker first
                try:
                    pending.result()
                except (OSError, RuntimeError, ValueError):
                    pass
            pool.shutdown(wait=True)
            src.close()

        report = StreamReport(
            path=path, shape=plan.shape, tile=plan.tile, n_tiles=plan.n_tiles,
            n_batches=plan.n_batches, batch_tiles=plan.batch_tiles, nbytes=nbytes,
            eb_abs=eb, predictor=predictor, backend=backend,
            mem_budget=int(mem_budget), peak_tracked_bytes=mem.peak,
            ru_maxrss_kb=int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss),
            enhanced=enhanced,
            reservoir_tiles=len(reservoir) if reservoir is not None else 0,
            retries=retries,
            failed_batches=tuple(sorted(failed_batches)),
            resumed_batches=resumed_batches,
            entropy_device=device_entropy,
        )
    report.stages = col.stages()
    report.host_stage_s = report.stages.get("gwlz.ingest.append", (0, 0.0, 0))[1]
    report.programs_compiled = col.compiles
    return report
