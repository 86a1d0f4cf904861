"""GatedGCN [Bresson & Laurent; Dwivedi benchmark 2003.00982].

h_i' = h_i + ReLU(Norm(U h_i + Σ_j η_ij ⊙ V h_j)),
η_ij = σ(ê_ij) / (Σ_j' σ(ê_ij') + ε),
ê_ij  = e_ij + ReLU(Norm(A h_i + B h_j + C e_ij)).

Edge gates are per-edge floats → the aggregation is inherently valued; B2SR
applies only to structure queries (DESIGN.md §Arch-applicability). Norm is
LayerNorm (stateless stand-in for the benchmark's BatchNorm — noted).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro import nn
from repro.configs.base import GNNConfig
from repro.models.gnn.common import GraphBatch, node_ce_loss

Params = Dict[str, Any]


def init_layer(key, d: int) -> Params:
    ks = jax.random.split(key, 5)
    return {
        "U": nn.dense_params(ks[0], d, d),
        "V": nn.dense_params(ks[1], d, d),
        "A": nn.dense_params(ks[2], d, d),
        "B": nn.dense_params(ks[3], d, d),
        "C": nn.dense_params(ks[4], d, d),
        "norm_h": nn.layer_norm_params(d),
        "norm_e": nn.layer_norm_params(d),
    }


def init_params(cfg: GNNConfig, key) -> Params:
    ks = jax.random.split(key, cfg.n_layers + 3)
    return {
        "embed_h": nn.dense_params(ks[0], cfg.d_in, cfg.d_hidden),
        "embed_e": nn.dense_params(ks[1], 1, cfg.d_hidden),
        "layers": [init_layer(ks[2 + i], cfg.d_hidden)
                   for i in range(cfg.n_layers)],
        "head": nn.dense_params(ks[-1], cfg.d_hidden, cfg.n_classes),
    }


def _layer_agg_dense(lp, h, e, batch, n):
    """Reference gather/scatter aggregation (GSPMD decides the comms)."""
    hs = h[batch.senders]
    hr = h[batch.receivers]
    e_hat = e + jax.nn.relu(nn.layer_norm(
        lp["norm_e"],
        nn.dense(lp["A"], hr) + nn.dense(lp["B"], hs) + nn.dense(lp["C"], e)))
    sig = jax.nn.sigmoid(e_hat) * batch.edge_mask[:, None]
    denom = jax.ops.segment_sum(sig, batch.receivers, num_segments=n)
    msgs = sig * nn.dense(lp["V"], hs)
    agg = jax.ops.segment_sum(msgs, batch.receivers, num_segments=n)
    return e_hat, agg, denom


def _layer_agg_shardmap(lp, h, e, batch, cfg, n):
    """Receiver-partitioned aggregation (§Perf, EXPERIMENTS.md).

    Contract (data pipeline): edge arrays are receiver-sorted and padded so
    shard i's receivers fall in node block i. Each device all-gathers the
    (small-d) node features once, computes its edges locally, and
    scatter-adds into its own node block — no cross-device scatter, and the
    backward of the all-gather is a reduce-scatter.
    """
    from jax._src.mesh import thread_resources
    from jax.sharding import PartitionSpec as P

    mesh = thread_resources.env.physical_mesh
    axes = tuple(a for a in cfg.shardmap_agg_axes if a in mesh.axis_names)
    if not axes or mesh.empty:
        return _layer_agg_dense(lp, h, e, batch, n)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    p_total = 1
    for a in axes:
        p_total *= sizes[a]
    if n % p_total != 0:
        return _layer_agg_dense(lp, h, e, batch, n)
    n_local = n // p_total
    msg_dtype = (jnp.bfloat16 if cfg.message_dtype == "bfloat16"
                 else h.dtype)

    def block(lp_, h_blk, e_blk, snd, rcv, msk):
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * sizes[a] + jax.lax.axis_index(a)
        h_full = jax.lax.all_gather(h_blk.astype(msg_dtype), axes,
                                    axis=0, tiled=True)
        hs = h_full[snd]
        hr = h_full[rcv]
        e_hat = e_blk + jax.nn.relu(nn.layer_norm(
            lp_["norm_e"],
            (nn.dense(lp_["A"], hr) + nn.dense(lp_["B"], hs)).astype(e_blk.dtype)
            + nn.dense(lp_["C"], e_blk)))
        sig = jax.nn.sigmoid(e_hat) * msk[:, None]
        r_local = jnp.clip(rcv - idx * n_local, 0, n_local - 1)
        sig32 = sig.astype(jnp.float32)
        denom = jax.ops.segment_sum(sig32, r_local, num_segments=n_local)
        msgs = sig32 * nn.dense(lp_["V"], hs).astype(jnp.float32)
        agg = jax.ops.segment_sum(msgs, r_local, num_segments=n_local)
        return e_hat, agg.astype(h_blk.dtype), denom.astype(h_blk.dtype)

    nspec = P(axes, None)
    espec = P(axes, None)
    mspec = P(axes)
    lp_specs = jax.tree_util.tree_map(lambda _: P(), lp)
    return jax.shard_map(
        block, mesh=mesh,
        in_specs=(lp_specs, nspec, espec, mspec, mspec, mspec),
        out_specs=(espec, nspec, nspec),
    )(lp, h, e, batch.senders, batch.receivers, batch.edge_mask)


def forward(params: Params, batch: GraphBatch, cfg: GNNConfig,
            pooled: bool = False) -> jax.Array:
    n = batch.node_feat.shape[0]
    h = nn.dense(params["embed_h"], batch.node_feat)
    if batch.edge_feat is not None:
        e = nn.dense(params["embed_e"], batch.edge_feat)
    else:
        e = jnp.zeros((batch.senders.shape[0], cfg.d_hidden), h.dtype)

    def layer_fn(lp, h, e):
        if cfg.shardmap_agg_axes:
            e_hat, agg, denom = _layer_agg_shardmap(lp, h, e, batch, cfg, n)
        else:
            e_hat, agg, denom = _layer_agg_dense(lp, h, e, batch, n)
        eta_agg = agg / jnp.maximum(denom, 1e-6)
        h = h + jax.nn.relu(nn.layer_norm(
            lp["norm_h"], nn.dense(lp["U"], h) + eta_agg))
        return h, e_hat

    if cfg.remat:
        layer_fn = jax.checkpoint(layer_fn)
    for lp in params["layers"]:
        h, e = layer_fn(lp, h, e)
    if pooled:
        from repro.models.gnn.common import graph_pool
        h = graph_pool(h, batch.graph_ids, batch.n_graphs, batch.node_mask)
    return nn.dense(params["head"], h)


def loss_fn(params: Params, batch: GraphBatch, cfg: GNNConfig):
    if batch.n_graphs > 1:  # graph-level task (molecule shape)
        logits = forward(params, batch, cfg, pooled=True)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, batch.labels[:, None], -1)[:, 0]
        loss = jnp.mean(logz - gold)
    else:
        logits = forward(params, batch, cfg)
        loss = node_ce_loss(logits, batch.labels, batch.train_mask)
    return loss, {"ce": loss}
