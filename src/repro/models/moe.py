"""Mixture-of-Experts FFN with static-capacity sort-based dispatch.

Top-k routing -> sort token-expert assignments by expert -> static-capacity
[E, C] gather -> batched expert matmuls -> weighted scatter-combine. All
shapes static (TPU/pjit friendly); tokens overflowing an expert's capacity
are dropped (standard Switch/GShard semantics, capacity_factor controls it).

Expert weights carry a leading E axis that shards over the "model" mesh axis
(expert parallelism); with tokens sharded over "data", XLA lowers the
gather/scatter to all-to-alls (the dispatch/combine collectives).

Arctic-style dense residual: an always-on dense FFN added to the routed
output (config.moe.dense_residual_d_ff).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro import nn

Params = Dict[str, Any]


def init_moe_params(cfg, key) -> Params:
    moe = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    e = moe.n_experts
    ff = moe.d_ff_expert
    p: Params = {
        "router": nn.dense_init(ks[0], d, e, scale=0.02),
        "w_gate": jax.random.normal(ks[1], (e, d, ff)) / jnp.sqrt(d),
        "w_up": jax.random.normal(ks[2], (e, d, ff)) / jnp.sqrt(d),
        "w_down": jax.random.normal(ks[3], (e, ff, d)) / jnp.sqrt(ff),
    }
    if moe.dense_residual_d_ff:
        dff = moe.dense_residual_d_ff
        kd = jax.random.split(ks[4], 3)
        p["dense_gate"] = nn.dense_init(kd[0], d, dff)
        p["dense_up"] = nn.dense_init(kd[1], d, dff)
        p["dense_down"] = nn.dense_init(kd[2], dff, d)
    return p


def capacity(n_tokens: int, moe) -> int:
    c = int(n_tokens * moe.top_k * moe.capacity_factor / moe.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly shapes


def moe_ffn(p: Params, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (y [B, S, d], aux_loss scalar).

    Dispatch strategy (§Perf, EXPERIMENTS.md): when a mesh with a "model"
    axis is active and ``cfg.moe_shardmap_dispatch`` is set, the routed part
    runs through the shard_map expert-parallel path (local dispatch against
    model-replicated activations + one psum combine); otherwise the global
    sort-based gather/scatter below (GSPMD decides the collectives).
    """
    if getattr(cfg, "moe_shardmap_dispatch", False) and cfg.batch_axes:
        out = _moe_ffn_shardmap(p, x, cfg)
        if out is not None:
            return out
    return _moe_ffn_dense(p, x, cfg)


def _moe_ffn_dense(p: Params, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    moe = cfg.moe
    B, S, d = x.shape
    N = B * S
    E, K = moe.n_experts, moe.top_k
    C = capacity(N, moe)
    xt = x.reshape(N, d)

    # --- routing (fp32 for numerics) ---
    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                       # [N, E]
    gate_vals, expert_idx = jax.lax.top_k(probs, K)               # [N, K]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # --- aux losses (Switch load-balance + router z-loss) ---
    me = jnp.mean(probs, axis=0)                                  # [E]
    ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E), axis=0)
    lb_loss = E * jnp.sum(me * ce) * moe.load_balance_loss
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * moe.router_z_loss
    aux = lb_loss + z_loss

    # --- sort-based static dispatch ---
    flat_expert = expert_idx.reshape(-1)                          # [N*K]
    flat_token = jnp.repeat(jnp.arange(N), K)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_expert)                              # stable
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
    starts = jnp.searchsorted(se, jnp.arange(E))                  # [E]
    pos_in_e = jnp.arange(N * K) - starts[se]
    keep = pos_in_e < C

    slot_e = jnp.where(keep, se, E)       # overflow -> dropped row E
    slot_c = jnp.where(keep, pos_in_e, 0)
    # token index per (E, C) slot; padded slots point at token 0 with gate 0
    dispatch = jnp.zeros((E + 1, C), jnp.int32).at[slot_e, slot_c].set(
        st.astype(jnp.int32), mode="drop")[:E]
    gates_ec = jnp.zeros((E + 1, C), jnp.float32).at[slot_e, slot_c].set(
        sg, mode="drop")[:E]

    # --- expert compute (batched over E; shards over "model") ---
    dtype = x.dtype
    xe = xt[dispatch]                                             # [E, C, d]
    gg = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(dtype))
    uu = jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(dtype))
    hh = jax.nn.silu(gg) * uu
    ye = jnp.einsum("ecf,efd->ecd", hh, p["w_down"].astype(dtype))
    ye = ye * gates_ec[..., None].astype(dtype)

    # --- combine (scatter-add back to tokens) ---
    y = jnp.zeros((N, d), dtype).at[dispatch.reshape(-1)].add(
        ye.reshape(-1, d))

    if moe.dense_residual_d_ff:
        y = y + (jax.nn.silu(xt @ p["dense_gate"].astype(dtype))
                 * (xt @ p["dense_up"].astype(dtype))) @ p["dense_down"].astype(dtype)
    return y.reshape(B, S, d), aux


def _moe_ffn_shardmap(p: Params, x: jax.Array, cfg):
    """Expert-parallel dispatch without cross-device gathers (§Perf).

    Mesh layout: tokens shard over the batch axes and REPLICATE over
    "model"; experts shard over "model". Device (i, j) therefore already
    holds every token of data-shard i — it routes locally, gathers only the
    tokens bound for ITS expert block (a local gather), runs the expert
    FFNs, scatter-adds into a local [N_loc, d] buffer, and a single
    psum over "model" combines the expert contributions. Per layer wire =
    2·N_loc·d bytes instead of the ~40× that GSPMD's one-hot global
    dispatch emits (measured, EXPERIMENTS.md §Perf qwen3 iteration 2).

    Capacity semantics: per (expert, data-shard) capacity C/n_data —
    standard local-capacity Switch semantics (drop patterns can differ
    from the global-capacity dense path at overflow; equal when nothing
    drops — tests/test_moe_shardmap.py).

    Returns None when the mesh/shape prerequisites don't hold (falls back).
    """
    from jax._src.mesh import thread_resources
    from jax.sharding import PartitionSpec as P

    moe = cfg.moe
    B, S, d = x.shape
    N = B * S
    E, K = moe.n_experts, moe.top_k
    mesh = thread_resources.env.physical_mesh
    if mesh.empty or "model" not in mesh.axis_names:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    data_axes = tuple(a for a in cfg.batch_axes if a in mesh.axis_names)
    if not data_axes:
        return None
    n_data = 1
    for a in data_axes:
        n_data *= sizes[a]
    n_model = sizes["model"]
    if E % n_model != 0 or B % n_data != 0:
        return None
    E_loc = E // n_model
    C_loc = max(8, -(-capacity(N, moe) // n_data // 8) * 8)
    dtype = x.dtype

    def block(router, wg, wu, wd, xb):
        # xb: [B_loc, S, d]; wg/wu/wd: [E_loc, ...]; router replicated
        B_loc = xb.shape[0]
        N_loc = B_loc * S
        xt = xb.reshape(N_loc, d)

        logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E), axis=0)
        lb_loss = E * jnp.sum(me * ce) * moe.load_balance_loss
        z_loss = (jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
                  * moe.router_z_loss)
        aux = jax.lax.pmean(lb_loss + z_loss, data_axes)

        # local-expert dispatch: same sort-based scheme, restricted to the
        # E_loc experts this model-shard owns
        j = jax.lax.axis_index("model")
        e_lo = j * E_loc
        flat_expert = expert_idx.reshape(-1)
        flat_token = jnp.repeat(jnp.arange(N_loc), K)
        flat_gate = gate_vals.reshape(-1)
        order = jnp.argsort(flat_expert)
        se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
        starts = jnp.searchsorted(se, jnp.arange(E))
        pos_in_e = jnp.arange(N_loc * K) - starts[se]
        local_e = se - e_lo
        keep = (local_e >= 0) & (local_e < E_loc) & (pos_in_e < C_loc)
        slot_e = jnp.where(keep, local_e, E_loc)
        slot_c = jnp.where(keep, pos_in_e, 0)
        dispatch = jnp.zeros((E_loc + 1, C_loc), jnp.int32).at[
            slot_e, slot_c].set(st.astype(jnp.int32), mode="drop")[:E_loc]
        gates_ec = jnp.zeros((E_loc + 1, C_loc), jnp.float32).at[
            slot_e, slot_c].set(sg, mode="drop")[:E_loc]

        xe = xt[dispatch]                                     # local gather
        gg = jnp.einsum("ecd,edf->ecf", xe, wg.astype(dtype))
        uu = jnp.einsum("ecd,edf->ecf", xe, wu.astype(dtype))
        hh = jax.nn.silu(gg) * uu
        ye = jnp.einsum("ecf,efd->ecd", hh, wd.astype(dtype))
        ye = ye * gates_ec[..., None].astype(dtype)

        y_part = jnp.zeros((N_loc, d), dtype).at[
            dispatch.reshape(-1)].add(ye.reshape(-1, d))
        y = jax.lax.psum(y_part, "model")                     # the combine
        return y.reshape(B_loc, S, d), aux

    xin = jax.lax.with_sharding_constraint(x, P(data_axes, None, None))
    y, aux = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(), P("model", None, None), P("model", None, None),
                  P("model", None, None), P(data_axes, None, None)),
        out_specs=(P(data_axes, None, None), P()),
    )(p["router"], p["w_gate"], p["w_up"], p["w_down"], xin)

    if moe.dense_residual_d_ff:
        xt = x.reshape(N, d)
        y_dense = (jax.nn.silu(xt @ p["dense_gate"].astype(dtype))
                   * (xt @ p["dense_up"].astype(dtype))
                   ) @ p["dense_down"].astype(dtype)
        y = y + y_dense.reshape(B, S, d)
    return y, aux
