"""Top-k MoE with sort-based capacity dispatch (GShard-style, no one-hot).

Routing: softmax router -> top-k experts per token -> counting-sort of
(token, expert) pairs -> positions within expert clamped at a static
capacity -> gather into a dense [E, C, D] buffer -> batched expert SwiGLU
-> weighted scatter-add back.  All data movement is gather/scatter (0
matmul FLOPs), so HLO FLOPs track *active* parameters: 6 * N_active * D.

Sharding: expert-stacked weights [E, ...] shard E over the "model" axis
(expert parallelism); the [E, C, D] dispatch buffer inherits that layout,
making the token->expert exchange an all-to-all under pjit.

Dropped tokens (capacity overflow) contribute zero output for the dropped
(token, expert) pair — the remaining top-k weights still apply, matching
capacity-factor semantics of GShard/Switch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.segments import segment_rank
from repro.models.config import ModelConfig
from repro.models.layers import dense_init, swiglu

__all__ = ["init_moe", "moe_block", "moe_capacity"]


def moe_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    """Static per-expert capacity: ceil(T*k/E * factor), MXU-aligned."""
    k, e = cfg.num_experts_per_tok, cfg.num_experts
    cap = int(n_tokens * k / e * cfg.capacity_factor) + 1
    return max(8, (cap + 7) // 8 * 8)


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, e), d, jnp.float32),
        "gate": dense_init(ks[1], (e, d, f), d, dtype),
        "up": dense_init(ks[2], (e, d, f), d, dtype),
        "down": dense_init(ks[3], (e, f, d), f, dtype),
    }


def _positions_within_expert(e_sorted: jnp.ndarray) -> jnp.ndarray:
    """Rank of each element within its (sorted, contiguous) expert run."""
    return segment_rank(e_sorted)


def moe_block(params: dict, cfg: ModelConfig, x: jnp.ndarray,
              capacity: int | None = None,
              constrain=None) -> tuple[jnp.ndarray, dict]:
    """x [B,S,D] -> ([B,S,D], aux metrics dict).

    When ``constrain`` carries a mesh with a >1 "model" axis and the expert
    count divides it, dispatch runs through the explicit shard_map EP path
    (`moe_block_ep`) — auto-sharded scatter/gather across the EP boundary
    makes GSPMD replicate the dispatch buffers, which is catastrophic at
    scale.  Otherwise the single-device reference path below runs.
    """
    ep = getattr(constrain, "ep_context", lambda: None)()
    if ep is not None and cfg.num_experts % ep[2] == 0:
        return moe_block_ep(params, cfg, x, constrain)
    b, s, d = x.shape
    t = b * s
    k, e = cfg.num_experts_per_tok, cfg.num_experts
    cap = capacity or moe_capacity(cfg, t)
    xf = x.reshape(t, d)

    # --- routing (f32 for numerics) ---------------------------------------
    logits = xf.astype(jnp.float32) @ params["router"]        # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)                          # [T, k]
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    # --- counting-sort dispatch -------------------------------------------
    e_flat = idx.reshape(-1).astype(jnp.int32)                # [T*k]
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    pos = _positions_within_expert(e_sorted)
    keep = pos < cap
    slot = jnp.where(keep, e_sorted * cap + pos, e * cap)     # overflow row
    token_of = (order // k).astype(jnp.int32)

    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    buf = buf.at[slot].set(xf[token_of], mode="drop")
    expert_in = buf[:e * cap].reshape(e, cap, d)

    # --- batched expert SwiGLU --------------------------------------------
    g = jnp.einsum("ecd,edf->ecf", expert_in, params["gate"])
    u = jnp.einsum("ecd,edf->ecf", expert_in, params["up"])
    expert_out = jnp.einsum("ecf,efd->ecd", swiglu(g, u), params["down"])

    # --- combine: weighted scatter-add back to tokens ---------------------
    flat_out = jnp.concatenate(
        [expert_out.reshape(e * cap, d), jnp.zeros((1, d), x.dtype)])
    gathered = flat_out[slot]                                  # [T*k, D]
    w_sorted = w.reshape(-1)[order].astype(x.dtype)
    contrib = gathered * jnp.where(keep, w_sorted, 0.0)[:, None]
    y = jnp.zeros((t, d), x.dtype).at[token_of].add(contrib)

    # --- aux: load-balance loss terms (Switch aux loss) --------------------
    me = probs.mean(0)                                         # [E]
    ce = jax.ops.segment_sum(jnp.ones_like(e_flat, jnp.float32), e_flat,
                             num_segments=e) / (t * k)
    aux = {
        "load_balance_loss": e * jnp.sum(me * ce),
        "dropped_frac": 1.0 - keep.mean(),
    }
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Explicit expert parallelism (shard_map)
# ---------------------------------------------------------------------------
def moe_block_ep(params: dict, cfg: ModelConfig, x: jnp.ndarray,
                 constrain) -> tuple[jnp.ndarray, dict]:
    """EP dispatch with shard_map: experts live on model-axis shards,
    tokens on DP shards (replicated over the model axis, as the residual
    stream already is under TP).  Each device routes its local tokens,
    keeps only the pairs destined to ITS local experts, runs the expert
    SwiGLU locally, and a single psum over the model axis sums the
    per-expert-shard partial outputs — the only collective on the MoE path
    beyond the FSDP weight all-gather.

    Capacity is per (device, local expert) with the same fill formula as
    the reference path; on a 1-device mesh the two paths are identical.
    """
    from functools import partial

    mesh, batch_axes, m_size = constrain.ep_context()
    model_axis = constrain._rules.model
    fsdp = constrain._rules.fsdp if constrain._rules.expert_fsdp else ()
    b, s, d = x.shape
    t = b * s
    k, e = cfg.num_experts_per_tok, cfg.num_experts
    e_loc = e // m_size

    # token sharding over DP axes (only if divisible)
    dp = tuple(a for a in batch_axes if a in mesh.axis_names)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    tok_spec = P(dp if (dp and t % dp_size == 0) else None, None)
    t_loc = t // dp_size if (dp and t % dp_size == 0) else t
    cap = moe_capacity(cfg, t_loc)

    w_specs = {
        "router": P(None, None),
        "gate": P(model_axis, fsdp if fsdp else None, None),
        "up": P(model_axis, fsdp if fsdp else None, None),
        "down": P(model_axis, None, fsdp if fsdp else None),
    }

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(tok_spec, w_specs),
             out_specs=(tok_spec, P()), check_vma=False)
    def ep(xf, w):
        # gather the FSDP dim of local expert weights (explicit ZeRO-3)
        gate, up, down = w["gate"], w["up"], w["down"]
        if fsdp:
            gate = jax.lax.all_gather(gate, fsdp, axis=1, tiled=True)
            up = jax.lax.all_gather(up, fsdp, axis=1, tiled=True)
            down = jax.lax.all_gather(down, fsdp, axis=2, tiled=True)

        logits = xf.astype(jnp.float32) @ w["router"]         # [Tl, E]
        probs = jax.nn.softmax(logits, axis=-1)
        wk, idx = jax.lax.top_k(probs, k)                     # [Tl, k]
        wk = wk / jnp.maximum(wk.sum(-1, keepdims=True), 1e-9)

        shard = jax.lax.axis_index(model_axis)
        e0 = shard * e_loc
        e_flat = idx.reshape(-1).astype(jnp.int32)
        local = (e_flat >= e0) & (e_flat < e0 + e_loc)
        e_local = jnp.where(local, e_flat - e0, e_loc)        # park others
        order = jnp.argsort(e_local, stable=True)
        e_sorted = e_local[order]

        # slot -> pair inversion (searchsorted): ONLY [e_loc*cap] indexing
        # tensors ever materialize — never the [T*k, D] gather.
        starts = jnp.searchsorted(e_sorted,
                                  jnp.arange(e_loc + 1, dtype=jnp.int32))
        slot_e = jnp.arange(e_loc * cap, dtype=jnp.int32) // cap
        slot_p = jnp.arange(e_loc * cap, dtype=jnp.int32) % cap
        pair = starts[slot_e] + slot_p                        # [e_loc*cap]
        valid = pair < starts[slot_e + 1]
        pair = jnp.minimum(pair, e_sorted.shape[0] - 1)
        token_slot = (order[pair] // k).astype(jnp.int32)     # [e_loc*cap]
        w_slot = wk.reshape(-1)[order[pair]].astype(xf.dtype)

        expert_in = jnp.where(valid[:, None], xf[token_slot], 0.0)
        expert_in = expert_in.reshape(e_loc, cap, -1)

        g = jnp.einsum("ecd,edf->ecf", expert_in, gate)
        u = jnp.einsum("ecd,edf->ecf", expert_in, up)
        expert_out = jnp.einsum("ecf,efd->ecd", swiglu(g, u), down)

        contrib = expert_out.reshape(e_loc * cap, -1) \
            * jnp.where(valid, w_slot, 0.0)[:, None]
        y = jnp.zeros_like(xf).at[token_slot].add(
            contrib, mode="drop")
        y = jax.lax.psum(y, model_axis)                       # combine
        keep = valid                                          # for metrics

        # aux metrics (global means via collectives)
        me = probs.mean(0)
        ce = jax.ops.segment_sum(
            jnp.ones_like(e_flat, jnp.float32), e_flat,
            num_segments=e) / (e_flat.shape[0])
        if dp:
            me = jax.lax.pmean(me, dp)
            ce = jax.lax.pmean(ce, dp)
        lb = e * jnp.sum(me * ce)
        kept = jax.lax.psum(keep.sum().astype(jnp.float32), model_axis)
        dropped = 1.0 - kept / e_flat.shape[0]
        if dp:
            dropped = jax.lax.pmean(dropped, dp)
        return y, {"load_balance_loss": lb, "dropped_frac": dropped}

    xf = x.reshape(t, d)
    y, aux = ep(xf, {k_: params[k_] for k_ in w_specs})
    return y.reshape(b, s, d), aux
