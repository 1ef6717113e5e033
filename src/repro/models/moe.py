"""Mixture-of-Experts with sort-based, static-shape token dispatch.

Dispatch strategy (TPU-native, all static shapes):
  1. top-k routing per token,
  2. stable argsort of the (token, expert) assignment list by expert id,
  3. per-expert capacity `cap` — tokens ranked past capacity are dropped
     (standard Switch/GShard semantics),
  4. scatter into an (E, cap, d) buffer -> batched expert einsum ->
     gather-combine weighted by router gates.

Under `experts -> 'model'` sharding the scatter/gather pair lowers to the
all-to-all family of collectives; tokens stay sharded over 'data'.

CFL hook: `expert_mask` (E,) disables a suffix of experts — the elastic
*expert-width* dimension of a CFL submodel (see core/submodel.py).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import _he, act_fn

NEG_INF = -2.0 ** 30


def moe_init(key, d_model, moe_cfg, gated=True):
    ks = jax.random.split(key, 5)
    E, f = moe_cfg.n_experts, moe_cfg.d_ff_expert
    p = {
        "router": _he(ks[0], (d_model, E), d_model),
        "wi": _he(ks[1], (E, d_model, f), d_model),
        "wo": _he(ks[2], (E, f, d_model), f),
    }
    if gated:
        p["wg"] = _he(ks[3], (E, d_model, f), d_model)
    if moe_cfg.n_shared:
        fs = f * moe_cfg.n_shared
        p["shared"] = {
            "wi": _he(ks[4], (d_model, fs), d_model),
            "wo": _he(jax.random.fold_in(ks[4], 1), (fs, d_model), fs),
        }
        if gated:
            p["shared"]["wg"] = _he(jax.random.fold_in(ks[4], 2),
                                    (d_model, fs), d_model)
    return p


def _dispatch_compute_combine(xt, gate_vals, idx, wi, wg, wo, *, E, k, cap,
                              act, expert_mask, e_offset=0, kernel=None):
    """Sort-based dispatch over (a slice of) experts — fully local math.

    xt: (T,d); idx/gate_vals: (T,k); wi/wg/wo: (E_loc,...) expert weights;
    e_offset: global id of this shard's first expert (shard_map path).
    Returns partial output (T,d): tokens not routed to local experts
    contribute zero (psum over 'model' reconstructs).

    kernel: optional grouped-matmul op (repro.kernels.dispatch 'moe'
    contract) — expert blocks past the active prefix are then *skipped*
    (the router never dispatches to them; see moe_forward), not merely
    zeroed by ``expert_mask``. When the op carries ``.dispatch`` /
    ``.combine`` (the dispatch table's ops do), the wide (·,d) token
    gather/scatter around the matmul runs as Pallas gather-reduce kernels
    too (``kernels.moe_dispatch``) — row movement, like the matmul tiles,
    is then proportional to what the router routed, forward and backward.
    """
    T, d = xt.shape
    E_loc = wi.shape[0]
    a = act_fn(act)

    e_flat = idx.reshape(-1) - e_offset                  # (T*k,) local ids
    valid = (e_flat >= 0) & (e_flat < E_loc)
    sort_key = jnp.where(valid, e_flat, E_loc)
    order = jnp.argsort(sort_key, stable=True)
    se = sort_key[order]
    token_of = order // k
    gate_of = gate_vals.reshape(-1)[order]
    start = jnp.searchsorted(se, jnp.arange(E_loc), side="left")
    pos_in_e = jnp.arange(T * k) - start[jnp.minimum(se, E_loc - 1)]
    # masked experts (the elastic suffix) count as dropped: their slots
    # stay empty and their assignments carry gate 0 on every path below
    ga_i = E_loc if expert_mask is None else \
        jnp.sum(expert_mask > 0).astype(jnp.int32)
    kept = (se < ga_i) & (pos_in_e < cap)
    dest = jnp.where(kept, se * cap + pos_in_e, E_loc * cap)

    # slot-centric formulation: all wide (·,d) gathers/scatters are sized by
    # the capacity buffer (E_loc*cap), never by T*k — the only T*k-sized
    # arrays are scalar index/gate vectors.
    n_slots = E_loc * cap
    slot_src = jnp.full((n_slots + 1,), T, jnp.int32).at[dest].set(
        token_of.astype(jnp.int32), mode="drop")[:-1]
    slot_gate = jnp.zeros((n_slots + 1,), xt.dtype).at[dest].set(
        (kept * gate_of).astype(xt.dtype), mode="drop")[:-1]

    disp = getattr(kernel, "dispatch", None)
    comb = getattr(kernel, "combine", None)
    if disp is not None and comb is not None:
        # the (t,j)-ordered transpose of the slot tables: the VJPs run
        # each direction's gather as the other's gather-reduce
        dest_tj = jnp.zeros((T * k,), jnp.int32).at[order].set(
            dest.astype(jnp.int32))
        kept_tj = jnp.zeros((T * k,), jnp.int32).at[order].set(
            kept.astype(jnp.int32))
        slot_valid = (slot_src < T).astype(jnp.int32)
        eb = disp(xt, slot_src, slot_valid, dest_tj, kept_tj,
                  n_experts=E_loc, cap=cap)
    else:
        xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
        eb = xt_pad[jnp.minimum(slot_src, T)].reshape(E_loc, cap, d)

    if kernel is not None:
        g_active = None if expert_mask is None else ga_i
        h = kernel(eb, wi, g_active)
        if wg is not None:
            h = a(kernel(eb, wg, g_active)) * h
        else:
            h = a(h)
        y = kernel(h, wo, g_active)
    else:
        h = jnp.einsum("ecd,edf->ecf", eb, wi.astype(xt.dtype))
        if wg is not None:
            h = a(jnp.einsum("ecd,edf->ecf", eb, wg.astype(xt.dtype))) * h
        else:
            h = a(h)
        y = jnp.einsum("ecf,efd->ecd", h, wo.astype(xt.dtype))
    if expert_mask is not None:
        y = y * expert_mask[:, None, None].astype(y.dtype)

    y_flat = y.reshape(n_slots, d)
    if disp is not None and comb is not None:
        gate_eff = gate_vals * kept_tj.reshape(T, k).astype(gate_vals.dtype)
        return comb(y_flat, gate_eff, dest_tj, slot_src, slot_valid,
                    slot_gate)
    y_flat = y_flat * slot_gate[:, None]
    return jnp.zeros((T + 1, d), xt.dtype).at[slot_src].add(
        y_flat, mode="drop")[:-1]


def moe_forward(p, x, moe_cfg, *, act="silu",
                expert_mask: Optional[jax.Array] = None, kernel=None):
    """x: (B, S, d). Returns (y, aux) with aux = {aux_loss, z_loss}.

    kernel: optional grouped elastic matmul (tile-skipping expert-prefix
    compute); used on the single-process path only — the shard_map branch
    keeps its einsums (expert compute there is already sliced to the
    local expert shard).

    Expert compute runs under shard_map when a mesh with a 'model' axis is
    ambient: activations are replicated over 'model' in the TP layout, so
    each model rank dispatches its local tokens to its *local* experts with
    zero communication and a single psum over 'model' combines — the
    dynamic scatter never crosses device boundaries (GSPMD would otherwise
    replicate the dispatch buffers).
    """
    from jax.sharding import PartitionSpec as P
    B, S, d = x.shape
    E, k = moe_cfg.n_experts, moe_cfg.top_k
    T = B * S
    xt = x.reshape(T, d)

    logits = (xt @ p["router"].astype(x.dtype)).astype(jnp.float32)
    if expert_mask is not None:
        logits = jnp.where(expert_mask[None, :] > 0, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)

    gate_vals, idx = jax.lax.top_k(probs, k)             # (T,k)
    gate_vals = (gate_vals /
                 jnp.sum(gate_vals, -1, keepdims=True)).astype(x.dtype)

    # --- aux losses (load balance + router z) -----------------------------
    # the balance coefficient counts *active* experts: under a CFL expert
    # mask the masked experts contribute zero to me/ce, and the extracted
    # submodel (n_exp experts) scales by n_exp — using parent E here would
    # make the masked loss diverge from the sliced one
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1), axis=0)
    n_active = (float(E) if expert_mask is None
                else jnp.sum(expert_mask > 0).astype(jnp.float32))
    aux_loss = moe_cfg.aux_loss * n_active * jnp.sum(me * ce)
    z_loss = moe_cfg.router_z_loss * jnp.mean(
        jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    # --- expert compute (sharded when possible) ---------------------------
    mesh = jax.sharding.get_abstract_mesh()
    names = set(mesh.axis_names)
    msize = mesh.shape["model"] if "model" in names else 1
    wg = p.get("wg")

    if "model" in names and E % msize == 0 and msize > 1:
        dp_axes = tuple(a for a in ("pod", "data") if a in names)
        dp = 1
        for a_ in dp_axes:
            dp *= mesh.shape[a_]
        bspec = dp_axes if (dp > 1 and T % dp == 0) else None
        T_loc = T // dp if bspec else T
        cap = int(math.ceil(T_loc * k / E * moe_cfg.capacity_factor))
        cap = max(8, -(-cap // 8) * 8)
        E_loc = E // msize

        shared = p.get("shared")

        def f(xt_l, gv_l, idx_l, wi_l, wg_l, wo_l, em_l, sh_l):
            r = jax.lax.axis_index("model")
            out = _dispatch_compute_combine(
                xt_l, gv_l, idx_l, wi_l,
                wg_l if wg is not None else None, wo_l,
                E=E, k=k, cap=cap, act=act,
                expert_mask=em_l, e_offset=r * E_loc)
            if shared is not None:
                # shared experts fused into the same region: their TP
                # partial sum rides the one combine psum (merges two
                # per-layer all-reduces into one)
                a = act_fn(act)
                hs = xt_l @ sh_l["wi"].astype(xt_l.dtype)
                if "wg" in sh_l:
                    hs = a(xt_l @ sh_l["wg"].astype(xt_l.dtype)) * hs
                else:
                    hs = a(hs)
                out = out + hs @ sh_l["wo"].astype(xt_l.dtype)
            return jax.lax.psum(out, "model")

        tok_spec = P(bspec, None)
        w_spec = P("model", None, None)
        em = expert_mask if expert_mask is not None else jnp.ones(
            (E,), jnp.float32)
        sh_specs = None
        sh_arg = 0.0
        if shared is not None:
            sh_specs = {kk: P(None, "model") if kk in ("wi", "wg")
                        else P("model", None) for kk in shared}
            sh_arg = shared
        out = jax.shard_map(
            f, mesh=mesh,
            in_specs=(tok_spec, tok_spec, tok_spec, w_spec, w_spec, w_spec,
                      P("model"), sh_specs if sh_specs else P()),
            out_specs=tok_spec, check_vma=False,
        )(xt, gate_vals, idx, p["wi"],
          wg if wg is not None else p["wi"], p["wo"], em, sh_arg)
        if shared is not None:
            return out.reshape(B, S, d), {"aux_loss": aux_loss,
                                          "z_loss": z_loss}
    else:
        # per-cohort capacity: size per-expert slots by the experts the
        # cohort can actually use (capacity_experts, default all of E)
        e_cap = moe_cfg.capacity_experts or E
        cap = int(math.ceil(T * k / e_cap * moe_cfg.capacity_factor))
        cap = max(8, -(-cap // 8) * 8)
        out = _dispatch_compute_combine(
            xt, gate_vals, idx, p["wi"], wg, p["wo"], E=E, k=k, cap=cap,
            act=act, expert_mask=expert_mask, kernel=kernel)

    # --- shared (always-on) experts ----------------------------------------
    if "shared" in p:
        sp = p["shared"]
        a = act_fn(act)
        hs = xt @ sp["wi"].astype(x.dtype)
        if "wg" in sp:
            hs = a(xt @ sp["wg"].astype(x.dtype)) * hs
        else:
            hs = a(hs)
        out = out + hs @ sp["wo"].astype(x.dtype)

    return out.reshape(B, S, d), {"aux_loss": aux_loss, "z_loss": z_loss}
