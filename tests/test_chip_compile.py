"""Main-path programs compiled for a described TPU v5e, at real widths.

No chip is needed: the TPU compiler runs here against a described v5e
topology and refuses what the chip would refuse (block shapes off the 8x128
tiling, ops Mosaic cannot lower, programs over the 16 GiB of HBM).  Passing
is not a chip run; ``chip_smoke.py`` is.

The topology is described inside a module fixture, never at import time:
only the worker that runs this file loads the TPU library.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

HBM_BYTES = 16 << 30  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # any failure to describe the chip means: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips():
    """A 1-D ``tiles`` mesh over a described 2x2 v5e host."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import numpy as np
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # any failure to describe the chip means: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return jax.sharding.Mesh(np.asarray(topo.devices), ("tiles",))


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_lorenzo_quant_tiles_compiles(one_chip):
    from repro.kernels.lorenzo_quant import lorenzo_quant_tiles

    c = _compile(lambda x: lorenzo_quant_tiles(x, 0.01, interpret=False),
                 _spec(one_chip, (8, 64, 64, 64)))
    assert _has_kernel(c)


def test_huffman_encode_pack_compiles(one_chip):
    """One 64^3 lane: 1024 chunks of 256 symbols."""
    from repro.kernels.huffman_encode import huffman_encode_pack

    lanes = _spec(one_chip, (1024, 256), jnp.int32)
    c = _compile(lambda l, w: huffman_encode_pack(l, w, interpret=False),
                 lanes, lanes)
    assert _has_kernel(c)


@pytest.mark.parametrize("n_bins", [32, 256, 4096],
                         ids=["33-bins", "257-bins", "4097-bins"])
def test_symbol_hist_compiles(one_chip, n_bins):
    """Alphabet spans up to the entropy stage's ``_ACCEL_SPAN`` (the bin
    count includes the out-of-range sentinel)."""
    from repro.kernels import ops

    c = _compile(lambda s: ops.symbol_hist_op(s, n_bins=n_bins, use_pallas=True,
                                              interpret=False),
                 _spec(one_chip, (64 ** 3,), jnp.int32))
    assert _has_kernel(c)


@pytest.mark.parametrize("n_ids,n", [(6, 1024), (3, 512)])
def test_huffman_decode_probe_compiles(one_chip, n_ids, n):
    """One 64^3 lane: 1024 chunk windows of 128 words, the 12-bit LUT; a
    1024-symbol alphabet with six ids per probe, and a 512-symbol one with
    three (the dark-matter field's commonest table)."""
    from repro.kernels.huffman_decode import LUT_ROWS, huffman_decode_probe

    i32 = partial(_spec, one_chip, dtype=jnp.int32)
    k = 12
    c = _compile(lambda *a: huffman_decode_probe(*a, chunk_size=256, k=k,
                                                 n_ids=n_ids, interpret=False),
                 i32((1024, 128)), i32((1024, 1)), i32((1024, 1)),
                 i32((LUT_ROWS, 1 << k)), i32((1, n)), i32((1, n)), i32((1, n)))
    assert _has_kernel(c)


def _enhancer_specs(sharding, G=20, C=9):
    from repro.core import enhancer

    params = jax.eval_shape(lambda: jax.vmap(lambda key: enhancer.init_params(key, C))(
        jax.random.split(jax.random.PRNGKey(0), G)))
    params = jax.tree.map(lambda a: _spec(sharding, a.shape, a.dtype), params)
    bn = {"mean": _spec(sharding, (G, C)), "var": _spec(sharding, (G, C))}
    return params, bn


def _group_specs(sharding, G):
    return {"id": _spec(sharding, (G,), jnp.int32), "lo": _spec(sharding, (G,)),
            "scale": _spec(sharding, (G,)), "rscale": _spec(sharding, (G,))}


@pytest.mark.parametrize("tiles", [32, 128])
@pytest.mark.parametrize("program", ["_gate_groups", "_bn_calibrate"])
def test_enhancer_pass_fits_hbm(one_chip, program, tiles):
    """BN calibration and the gate over a reservoir of 64^3 tiles at the
    published width (G=20, C=9): 32 tiles is the default reservoir at a
    256 MiB budget, 128 tiles a 1 GiB one."""
    from repro.core import trainer

    G = 20
    params, bn = _enhancer_specs(one_chip, G=G)
    n = tiles * 64
    xs = _spec(one_chip, (n, 64, 64))
    ids = _spec(one_chip, (n, 64, 64), jnp.int32)
    groups = _group_specs(one_chip, G)
    if program == "_gate_groups":
        fn = trainer._gate_groups
        args = (params, bn, xs, xs, ids, groups)
    else:
        fn = trainer._bn_calibrate
        args = (params, xs, ids, groups)
    m = _compile(fn, *args).memory_analysis()
    used = m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes
    assert used < HBM_BYTES, f"{program} at {tiles} tiles needs {used / 2**30:.2f} GiB"


def test_train_step_fits_hbm(one_chip):
    """One enhancer training step at the cells' shape (batch 10 of 64x64
    slices, C=9) for one block of the 20 groups, the program every device
    runs (``trainer.GROUP_BLOCKS``)."""
    from repro.core import trainer
    from repro.optim import AdamWConfig

    groups = 20 // trainer.GROUP_BLOCKS
    params, bn = _enhancer_specs(one_chip, G=groups)
    opt = {"step": _spec(one_chip, (), jnp.int32), "m": params, "v": params}
    batch = _spec(one_chip, (10, 64, 64))
    fn = partial(trainer.train_step, residual_learning=True, adam_cfg=AdamWConfig())
    m = _compile(fn, params, bn, opt, batch, batch,
                 _spec(one_chip, (10, 64, 64), jnp.int32),
                 _group_specs(one_chip, groups), _spec(one_chip, ())).memory_analysis()
    used = m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes
    assert used < HBM_BYTES, f"train_step at G={groups} needs {used / 2**30:.2f} GiB"


def test_enhance_tiles_mapped_runs_the_kernel(one_chip, monkeypatch):
    """Full decode's enhancer program at the cells' shape: one bucket of 32
    64^3 tiles (the bucket cap), G=20, C=9, clamped, on the chip's path (the
    grouped Pallas kernel, which the program picks on a TPU backend)."""
    from repro.core import trainer
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    G = 20
    params, bn = _enhancer_specs(one_chip, G=G)
    fn = partial(trainer._enhance_tiles_mapped, n_groups=G, residual_learning=True,
                 slice_axis=0, batch=64, use_clamp=True)
    c = _compile(fn, params, bn, _spec(one_chip, (32, 64, 64, 64)),
                 _spec(one_chip, (G + 1,)), _spec(one_chip, (G,)), _spec(one_chip, ()))
    assert _has_kernel(c)
    m = c.memory_analysis()
    used = m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes
    assert used < HBM_BYTES, f"_enhance_tiles_mapped needs {used / 2**30:.2f} GiB"


def test_enhance_tiles_over_four_chips_runs_the_kernel_per_chip(four_chips, monkeypatch):
    """Full decode's enhancer program on a four-chip host: the tile bucket
    split over the ``tiles`` mesh, the grouped kernel on each chip.  Handed
    mesh-sharded tiles, the jitted program alone is refused (a Mosaic kernel
    cannot be partitioned automatically); ``sharding.map_tiles`` runs it per
    chip."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import trainer
    from repro.kernels import ops
    from repro.launch import sharding

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    G = 20
    split, whole = NamedSharding(four_chips, P("tiles")), NamedSharding(four_chips, P())
    params, bn = _enhancer_specs(whole, G=G)
    fn = trainer._tile_enhancer(G, True, 0, 64, True)
    args = (_spec(split, (32, 64, 64, 64)), params, bn, _spec(whole, (G + 1,)),
            _spec(whole, (G,)), _spec(whole, ()))
    c = sharding._shard_mapped(fn, four_chips, 5).lower(*args).compile()
    assert _has_kernel(c)
    m = c.memory_analysis()
    used = m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes
    assert used < HBM_BYTES, f"per-chip enhancer needs {used / 2**30:.2f} GiB"
    with pytest.raises(Exception, match="automatically partitioned"):
        jax.jit(fn).lower(*args).compile()


def test_lorenzo_tiles_over_four_chips_compile_as_one_program(four_chips, monkeypatch):
    """The streamed ingest's tile batch (8 64^3 tiles) over a four-chip
    host: ``map_tiles`` compiles the Lorenzo kernel's per-chip program once,
    as one jitted ``shard_map``."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops
    from repro.launch import sharding
    from repro.sz import predictor

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    tiles = _spec(NamedSharding(four_chips, P("tiles")), (8, 64, 64, 64))
    fn = predictor._lorenzo_encoder(0.01, None)
    assert predictor._lorenzo_encoder(0.01, None) is fn
    c = sharding._shard_mapped(fn, four_chips, 0).lower(tiles).compile()
    assert _has_kernel(c)
