"""Mixture-of-Experts FFN with grouped sort-based dispatch (GShard layout).

Tokens are processed in groups (group axis = batch, sharded over the data
mesh axes); each group sorts its tokens by routed expert and scatters them
into a fixed-capacity (E, C) buffer.  Expert weights are sharded over the
``model`` mesh axis, so the dispatched tensor (G, E, C, D) reshards
group<->expert with an all-to-all inserted by GSPMD — the canonical
expert-parallel pattern, visible in the dry-run HLO and counted in the
collective roofline term.

Routing: softmax top-k with probability renormalization; capacity dropping
(tokens beyond C per expert in a group are dropped = contribute zero, the
residual connection carries them through).  Aux load-balancing loss
(Switch) is returned for the train loss.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.utils.sharding import shard as _shard


def shard(x, spec):
    """Constraint with a baseline escape hatch for §Perf A/B runs."""
    if os.environ.get("REPRO_MOE_NO_CONSTRAIN"):
        return x
    return _shard(x, spec)


def route_topk(router_logits: jax.Array, top_k: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> (expert_idx (..., k), combine_w (..., k), aux_loss ())."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # Switch aux loss: E * sum_e (fraction_tokens_e * mean_prob_e)
    e = router_logits.shape[-1]
    one_hot = jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32)
    frac = jnp.mean(one_hot.reshape(-1, e), axis=0)
    mean_p = jnp.mean(probs.reshape(-1, e), axis=0)
    aux = e * jnp.sum(frac * mean_p)
    return idx, w.astype(router_logits.dtype), aux


def dispatch_indices(expert_idx: jax.Array, n_experts: int, capacity: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """Per group: sort token-slots by expert, assign capacity positions.

    expert_idx: (T, k) int32 for one group of T tokens.
    Returns (slot_expert (T*k,), slot_pos (T*k,)); slot_pos == capacity
    marks a dropped slot.
    """
    t, k = expert_idx.shape
    flat_e = expert_idx.reshape(-1)                       # (T*k,)
    # stable sort by expert keeps earlier tokens first (priority = order)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # position within its expert = running index - first index of expert
    idx_in_sorted = jnp.arange(t * k)
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(n_experts),
                                 side="left")
    pos_sorted = idx_in_sorted - seg_start[sorted_e]
    pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)
    pos = jnp.minimum(pos, capacity)                      # cap -> dropped
    return flat_e, pos


def moe_ffn_shard_map(x: jax.Array, router_w: jax.Array,
                      w1: jax.Array, w3: jax.Array, w2: jax.Array,
                      top_k: int, capacity: int, mesh,
                      group_axes, expert_axis: str,
                      fsdp_axis: Optional[str] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Manual-collective MoE: dispatch/combine local, ONE psum per layer.

    GSPMD partitions the dispatch scatter/gather poorly (measured on
    granite train_4k: ~10 GB/chip/layer of all-gather/all-reduce around
    the scatter).  Under shard_map every device:
      1. routes and scatters ITS token groups into a full-E capacity
         buffer (identical work across the model axis — scatters are
         cheap, O(T*k*D) writes),
      2. computes ONLY its expert slice (E/model) of the FFN,
      3. combines its experts' outputs back per token,
      4. psum over the model axis merges expert contributions:
         (G_loc, T, D) bf16 — the only cross-device traffic.
    Expert weight grads stay fully local to their model shard.

    With fsdp_axis set (llama4), expert weights arrive D-sharded and are
    all-gathered layer-locally (standard FSDP weight gather).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec
    g, t, d = x.shape
    e = router_w.shape[-1]
    gspec = PartitionSpec(group_axes, None, None)
    w_spec = PartitionSpec(expert_axis, fsdp_axis, None)
    w2_spec = PartitionSpec(expert_axis, None, fsdp_axis)

    def local_fn(x_loc, router_loc, w1_loc, w3_loc, w2_loc):
        gl = x_loc.shape[0]
        e_loc = w1_loc.shape[0]
        e0 = jax.lax.axis_index(expert_axis) * e_loc
        if fsdp_axis is not None:
            w1_loc = jax.lax.all_gather(w1_loc, fsdp_axis, axis=1,
                                        tiled=True)
            w3_loc = jax.lax.all_gather(w3_loc, fsdp_axis, axis=1,
                                        tiled=True)
            w2_loc = jax.lax.all_gather(w2_loc, fsdp_axis, axis=2,
                                        tiled=True)
        logits = jnp.einsum("gtd,de->gte", x_loc, router_loc,
                            preferred_element_type=jnp.float32)
        expert_idx, combine_w, aux = route_topk(logits, top_k)

        def one_group(xg, idxg, wg):
            slot_e, slot_pos = dispatch_indices(idxg, e, capacity)
            tok_of_slot = jnp.repeat(jnp.arange(t), top_k)
            # local expert slice only: remap expert ids, mask the rest
            le = slot_e - e0
            mine = (le >= 0) & (le < e_loc) & (slot_pos < capacity)
            le_c = jnp.clip(le, 0, e_loc - 1)
            sp_c = jnp.minimum(slot_pos, capacity - 1)
            buf = jnp.zeros((e_loc, capacity, d), xg.dtype)
            upd = jnp.where(mine[:, None], xg[tok_of_slot], 0.0)
            buf = buf.at[le_c, sp_c].add(upd)     # masked rows add zero
            return buf, le_c, sp_c, mine, tok_of_slot

        buf, le_c, sp_c, mine, tok_of_slot = jax.vmap(one_group)(
            x_loc, expert_idx, combine_w)          # (Gl, E_loc, C, D)
        h1 = jnp.einsum("gecd,edf->gecf", buf, w1_loc)
        h3 = jnp.einsum("gecd,edf->gecf", buf, w3_loc)
        h = jax.nn.silu(h1.astype(jnp.float32)).astype(h1.dtype) * h3
        y = jnp.einsum("gecf,efd->gecd", h, w2_loc)

        def one_combine(yg, le, sp, ok, ts, wg):
            vals = yg[le, sp]
            vals = jnp.where(ok[:, None], vals, 0.0)
            wflat = wg.reshape(-1)[:, None].astype(vals.dtype)
            return jax.ops.segment_sum(vals * wflat, ts, t)

        out = jax.vmap(one_combine)(y, le_c, sp_c, mine, tok_of_slot,
                                    combine_w)
        out = jax.lax.psum(out, expert_axis)       # the ONE collective
        aux = jax.lax.pmean(aux, expert_axis)
        if group_axes:
            aux = jax.lax.pmean(aux, group_axes)
        return out, aux

    kwargs = dict(
        mesh=mesh,
        in_specs=(gspec, PartitionSpec(None, None), w_spec, w_spec,
                  w2_spec),
        out_specs=(gspec, PartitionSpec()))
    try:
        # decode (group_axes=None) computes replicated outputs the
        # checker cannot statically verify
        fn = shard_map(local_fn, check_vma=False, **kwargs)
    except TypeError:              # older jax spelling
        fn = shard_map(local_fn, check_rep=False, **kwargs)
    return fn(x, router_w, w1, w3, w2)


def moe_ffn(x: jax.Array, router_w: jax.Array,
            w1: jax.Array, w3: jax.Array, w2: jax.Array,
            top_k: int, capacity: int,
            group_axes=None, expert_axis: Optional[str] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """Grouped MoE SwiGLU FFN.

    x: (G, T, D) — G groups (sharded over ``group_axes``), T tokens per
    group.  router_w: (D, E); w1/w3: (E, D, F); w2: (E, F, D) — experts
    sharded over ``expert_axis``.
    -> (out (G, T, D), aux_loss ()).

    Explicit sharding constraints pin the expert-parallel dataflow:
    dispatch/expert/combine tensors stay GROUP-sharded over the data
    axes and EXPERT-sharded over the model axis, so the only collectives
    are the (small) per-layer expert-weight/output exchanges — without
    them GSPMD replicated the (G,E,C,D) dispatch buffer across the data
    axis (measured: 21.5 GB/layer/chip all-gather on granite train_4k).
    """
    g, t, d = x.shape
    e = router_w.shape[-1]

    def gspec(*rest) -> P:
        return P(group_axes, *rest) if (group_axes or expert_axis) else P()

    logits = jnp.einsum("gtd,de->gte", x, router_w,
                        preferred_element_type=jnp.float32)
    expert_idx, combine_w, aux = route_topk(logits, top_k)

    def one_group(xg, idxg, wg):
        # xg: (T, D), idxg: (T, k), wg: (T, k)
        slot_e, slot_pos = dispatch_indices(idxg, e, capacity)
        tok_of_slot = jnp.repeat(jnp.arange(t), top_k)
        # scatter tokens into the (E, C+1, D) buffer (C index = drop bin)
        buf = jnp.zeros((e, capacity + 1, d), xg.dtype)
        buf = buf.at[slot_e, slot_pos].set(xg[tok_of_slot])
        return buf[:, :capacity], slot_e, slot_pos, tok_of_slot

    buf, slot_e, slot_pos, tok_of_slot = jax.vmap(one_group)(
        x, expert_idx, combine_w)                         # (G,E,C,D)
    # buf stays EXPERT-REPLICATED: the scatter that builds it is local
    # per group, and propagating an expert sharding backward into the
    # scatter makes GSPMD all-gather the (G,T*k,D) update tensor instead
    buf = shard(buf, gspec(None, None, None))

    # expert computation: each model shard multiplies the replicated buf
    # by ITS expert slice -> h1/h3/y expert-sharded with zero resharding
    h1 = jnp.einsum("gecd,edf->gecf", buf, w1)
    h3 = jnp.einsum("gecd,edf->gecf", buf, w3)
    h1 = shard(h1, gspec(expert_axis, None, None))
    h3 = shard(h3, gspec(expert_axis, None, None))
    h = jax.nn.silu(h1.astype(jnp.float32)).astype(h1.dtype) * h3
    y = jnp.einsum("gecf,efd->gecd", h, w2)               # (G,E,C,D)
    y = shard(y, gspec(None, None, None))   # gather experts per group

    def one_combine(yg, se, sp, ts, wg):
        # gather back: each slot reads its expert/capacity cell; dropped
        # slots (sp == capacity) read the zero pad via clamping + mask.
        ok = sp < capacity
        vals = yg[se, jnp.minimum(sp, capacity - 1)]      # (T*k, D)
        vals = jnp.where(ok[:, None], vals, 0.0)
        wflat = wg.reshape(-1)[:, None].astype(vals.dtype)
        out = jax.ops.segment_sum(vals * wflat, ts, t)
        return out

    out = jax.vmap(one_combine)(y, slot_e, slot_pos, tok_of_slot, combine_w)
    return out.astype(x.dtype), aux
