"""Distributed GWLZ: the paper's group-wise enhancer training as an SPMD
program on the production mesh (DESIGN.md §3.3/§5).

Mapping: volume slices -> ``data`` axis (DP over the batch of slices),
enhancer group axis -> ``model`` axis (EP-style: each model shard owns
G/|model| groups — groups are independent, so no cross-group collectives
exist at all).  Gradients reduce over ``data``+``pod`` only, optionally with
the paper-derived error-bounded int8 compression (optim.grad_compress).

This module also provides the dry-run cell "gwlz-nyx / vol512" — the cell
most representative of the paper's own technique in EXPERIMENTS.md §Roofline.

It is a dry-run sketch.  The normal path (``api.compress_stream`` ->
``repro.core.trainer.train_enhancers``) splits the group axis over the tile
devices itself, one block of groups per device, with the slices copied to
every device rather than sharded.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import enhancer, grouping
from repro.core.trainer import GWLZTrainConfig, _group_inputs, _loss_one_group
from repro.optim import AdamWConfig, adamw
from repro.optim.grad_compress import GradCompressConfig, apply as gc_apply, init_ef


@dataclass(frozen=True)
class DistGWLZConfig:
    n_groups: int = 32          # pad to a multiple of the model-axis size
    channels: int = 9
    volume: int = 512           # Nyx: 512^3
    batch_slices: int = 64      # global slice batch per step
    lr: float = 1e-3
    grad_compress: bool = False
    gc_rel_eb: float = 1e-2


def build_state(cfg: DistGWLZConfig, key=None):
    key = jax.random.PRNGKey(0) if key is None else key
    G = cfg.n_groups
    pkeys = jax.random.split(key, G)
    params = jax.vmap(lambda k: enhancer.init_params(k, cfg.channels))(pkeys)
    bn = jax.vmap(lambda _: enhancer.init_state(cfg.channels))(jnp.arange(G))
    opt = adamw.init(params, AdamWConfig())
    ef = init_ef(params) if cfg.grad_compress else None
    return {"params": params, "bn": bn, "opt": opt, "ef": ef}


def make_dist_train_step(cfg: DistGWLZConfig, mesh):
    """Returns (train_step, in_shardings builder).

    train_step(state, batch) where batch = {"x": [B,H,W] decompressed slices,
    "r": [B,H,W] residuals, "edges": [G+1], "rscale": [G]}.
    """
    G = cfg.n_groups
    gc_cfg = GradCompressConfig(rel_eb=cfg.gc_rel_eb, enabled=cfg.grad_compress)
    adam_cfg = AdamWConfig()

    def train_step(state, batch):
        xb, rb = batch["x"], batch["r"]
        edges, rscale = batch["edges"], batch["rscale"]
        ids = grouping.assign_groups(xb, edges)
        xn, masks = _group_inputs(xb, ids, edges, G)
        safe = jnp.where(rscale > 0, rscale, 1.0)
        target = rb[None] / safe[:, None, None, None] * masks

        def lossfn(p):
            losses, states = jax.vmap(_loss_one_group)(p, state["bn"], xn, masks, target)
            return losses.sum(), (losses, states)

        grads, (losses, new_bn) = jax.grad(lossfn, has_aux=True)(state["params"])
        ef = state["ef"]
        if cfg.grad_compress:
            grads, ef = gc_apply(grads, ef, gc_cfg)
        params, opt = adamw.update(state["params"], state["opt"], grads, cfg.lr, adam_cfg)
        return {"params": params, "bn": new_bn, "opt": opt, "ef": ef}, losses

    # shardings: group-stacked leaves on "model"; slice batch on data axes
    from repro.launch.mesh import batch_axes_of

    baxes = batch_axes_of(mesh)

    def group_spec(leaf):
        if hasattr(leaf, "ndim") and leaf.ndim >= 1 and leaf.shape[0] == G:
            return P("model", *([None] * (leaf.ndim - 1)))
        return P()

    def state_shardings(state):
        return jax.tree.map(
            lambda l: NamedSharding(mesh, group_spec(l)), state,
            is_leaf=lambda l: hasattr(l, "shape"),
        )

    def batch_shardings(batch):
        return {
            "x": NamedSharding(mesh, P(baxes, None, None)),
            "r": NamedSharding(mesh, P(baxes, None, None)),
            "edges": NamedSharding(mesh, P(None)),
            "rscale": NamedSharding(mesh, P(None)),
        }

    return train_step, state_shardings, batch_shardings


def input_specs(cfg: DistGWLZConfig):
    """ShapeDtypeStructs for the dry-run cell (512^3 Nyx volume)."""
    V, B = cfg.volume, cfg.batch_slices
    f32 = jnp.float32
    return {
        "x": jax.ShapeDtypeStruct((B, V, V), f32),
        "r": jax.ShapeDtypeStruct((B, V, V), f32),
        "edges": jax.ShapeDtypeStruct((cfg.n_groups + 1,), f32),
        "rscale": jax.ShapeDtypeStruct((cfg.n_groups,), f32),
    }
