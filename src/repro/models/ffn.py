"""Dense FFN (SwiGLU/GELU) and MoE with sort-based ragged dispatch.

MoE uses argsort + ``jax.lax.ragged_dot`` (MegaBlocks-style grouped GEMM,
no GShard dispatch-einsum overhead, no token dropping) — expert weights
carry the 'experts' logical axis for expert parallelism.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import (ArchConfig, act_fn, leaf, linear,
                                 linear_init, param)


def ffn_init(key, cfg: ArchConfig, d_ff: int | None = None):
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w_up": linear_init(ks[0], cfg.d_model, d_ff, (None, "mlp")),
         "w_down": linear_init(ks[1], d_ff, cfg.d_model, ("mlp", None))}
    if cfg.act == "silu":                      # gated (SwiGLU)
        p["w_gate"] = linear_init(ks[2], cfg.d_model, d_ff, (None, "mlp"))
    return p


def ffn_apply(params, x, cfg: ArchConfig, policy, compute_dtype):
    up = linear(params["w_up"], x, policy, compute_dtype)
    if "w_gate" in params:
        gate = linear(params["w_gate"], x, policy, compute_dtype)
        h = jax.nn.silu(gate) * up           # compute-dtype elementwise:
    else:                                    # f32 here becomes a stacked
        h = act_fn(cfg.act)(up)              # f32 scan residual
    return linear(params["w_down"], h, policy, compute_dtype)


def moe_init(key, cfg: ArchConfig):
    ks = jax.random.split(key, 4)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    return {
        "router": linear_init(ks[0], d, e, (None, None)),
        "w_gate": param(ks[1], (e, d, f), ("experts", None, "mlp")),
        "w_up": param(ks[2], (e, d, f), ("experts", None, "mlp")),
        "w_down": param(ks[3], (e, f, d), ("experts", "mlp", None)),
    }


def moe_apply(params, x, cfg: ArchConfig, policy, compute_dtype):
    """Dispatch: EP shard_map when a distribution context is active (the
    sort-based path below replicates under SPMD — a global argsort cannot
    be partitioned), single-device sort+ragged_dot otherwise."""
    from repro.launch import context as dist_ctx
    ctx = dist_ctx.current()
    if ctx is not None and ctx.mesh.shape.get(ctx.ep, 1) > 1:
        return moe_apply_ep(params, x, cfg, policy, compute_dtype, ctx)
    return moe_apply_local(params, x, cfg, policy, compute_dtype)


def moe_apply_local(params, x, cfg: ArchConfig, policy, compute_dtype):
    """Returns (y, aux_loss).  x: (B, S, d)."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    flat = x.reshape(t, d)

    logits = linear(params["router"], flat, policy, jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                        # (T, k)
    top_w = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance aux loss.
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=0)
    aux = jnp.float32(e) * jnp.sum(frac_tokens * frac_probs)

    # sort token-expert pairs by expert id -> grouped GEMMs
    eid = top_e.reshape(t * k)
    order = jnp.argsort(eid)
    tok = order // k                                              # (T*k,)
    xs = jnp.take(flat, tok, axis=0).astype(compute_dtype)
    group_sizes = jnp.zeros((e,), jnp.int32).at[eid].add(1)

    def grouped(w):
        ww = policy.maybe_quantize_weights(leaf(w)).astype(compute_dtype)
        return lambda inp: jax.lax.ragged_dot(
            inp, ww, group_sizes, preferred_element_type=jnp.float32)

    gate = grouped(params["w_gate"])(xs)
    up = grouped(params["w_up"])(xs)
    h = (jax.nn.silu(gate) * up).astype(compute_dtype)
    out = grouped(params["w_down"])(h)                            # (T*k, d)

    w_sorted = jnp.take(top_w.reshape(t * k), order)
    out = out * w_sorted[:, None]
    y = jnp.zeros((t, d), jnp.float32).at[tok].add(out)
    return y.reshape(b, s, d).astype(compute_dtype), aux


# --------------------------------------------------------------------------
# expert parallelism (shard_map + capacity-bounded all_to_all)
# --------------------------------------------------------------------------

def moe_apply_ep(params, x, cfg: ArchConfig, policy, compute_dtype, ctx,
                 capacity_factor: float = 2.0):
    """GShard-style EP: tokens are routed to the EP shard owning their
    expert via a capacity-bounded all_to_all, processed by a local grouped
    GEMM (ragged_dot over E/P local experts), and routed back.

    shard_map is fully manual over (dp..., ep); per-device local shapes are
    real, so the two argsorts are LOCAL sorts — this is what the auto-SPMD
    sort-based path cannot express (it replicates; see dry-run log in
    EXPERIMENTS.md).  Overflowing tokens beyond the per-peer capacity are
    dropped (standard GShard semantics; aux loss keeps load balanced).
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh, dp, ep = ctx.mesh, ctx.dp, ctx.ep
    e, k = cfg.n_experts, cfg.top_k
    p_ep = mesh.shape[ep]
    e_local = e // p_ep
    assert e % p_ep == 0, (e, p_ep)

    manual = tuple(dp) + (ep,)

    router_w = leaf(params["router"]["w"])
    wg = policy.maybe_quantize_weights(leaf(params["w_gate"]))
    wu = policy.maybe_quantize_weights(leaf(params["w_up"]))
    wd = policy.maybe_quantize_weights(leaf(params["w_down"]))

    def local_moe(x_l, router_w, wg_l, wu_l, wd_l):
        b_l, s_l, d = x_l.shape
        t = b_l * s_l
        flat = x_l.reshape(t, d)
        my_peer = jax.lax.axis_index(ep)

        logits = jnp.dot(flat.astype(jnp.float32),
                         router_w.astype(jnp.float32))          # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)                  # (T, k)
        top_w = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        frac_tokens = jnp.mean(
            jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=(0, 1))
        frac_probs = jnp.mean(probs, axis=0)
        aux = jnp.float32(e) * jnp.sum(frac_tokens * frac_probs)

        tk = t * k
        eid = top_e.reshape(tk)
        wgt = top_w.reshape(tk)
        tok = jnp.arange(tk, dtype=jnp.int32) // k
        peer = eid // e_local

        # position of each pair within its destination-peer bucket, via
        # one-hot running counts (sort-free: SPMD-friendly, O(tk * p_ep))
        cap = max(int(capacity_factor * tk / p_ep), 8)
        oh = jax.nn.one_hot(peer, p_ep, dtype=jnp.int32)        # (tk, p_ep)
        pos = (jnp.cumsum(oh, axis=0) - oh)[
            jnp.arange(tk), peer]                               # (tk,)
        keep = pos < cap

        send_x = jnp.zeros((p_ep, cap, d), compute_dtype)
        send_x = send_x.at[peer, pos].set(
            jnp.where(keep[:, None], flat[tok].astype(compute_dtype), 0),
            mode="drop")
        send_eid = jnp.full((p_ep, cap), e, jnp.int32)          # e = invalid
        send_eid = send_eid.at[peer, pos].set(
            jnp.where(keep, eid, e), mode="drop")

        recv_x = jax.lax.all_to_all(send_x, ep, 0, 0, tiled=False)
        recv_eid = jax.lax.all_to_all(send_eid, ep, 0, 0, tiled=False)

        # regroup received tokens into dense per-expert capacity blocks —
        # blocked batched einsum instead of ragged_dot (whose SPMD/CPU
        # lowering expands to e_local full-size masked matmuls)
        n_recv = p_ep * cap
        rx = recv_x.reshape(n_recv, d)
        reid = recv_eid.reshape(n_recv) - my_peer * e_local
        valid = (reid >= 0) & (reid < e_local)
        reid_c = jnp.where(valid, reid, e_local)
        oh2 = jax.nn.one_hot(reid_c, e_local + 1, dtype=jnp.int32)
        pos2 = (jnp.cumsum(oh2, axis=0) - oh2)[
            jnp.arange(n_recv), reid_c]
        cap_e = max(int(1.5 * n_recv / e_local), 8)
        keep2 = valid & (pos2 < cap_e)

        blocks = jnp.zeros((e_local, cap_e, d), compute_dtype)
        blocks = blocks.at[reid_c, pos2].set(
            jnp.where(keep2[:, None], rx, 0), mode="drop")

        def expert_mm(w_l, inp):                                # (E_l,C,d)@(E_l,d,f)
            return jax.lax.dot_general(
                inp, w_l.astype(compute_dtype),
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)

        h = jax.nn.silu(expert_mm(wg_l, blocks)) * expert_mm(wu_l, blocks)
        hb = expert_mm(wd_l, h.astype(compute_dtype))           # (E_l,C,d)
        out_rows = jnp.where(keep2[:, None],
                             hb[reid_c, pos2].astype(compute_dtype), 0)
        out = out_rows.reshape(p_ep, cap, d)
        back = jax.lax.all_to_all(out, ep, 0, 0, tiled=False)   # (p_ep,cap,d)

        contrib = back[peer, pos].astype(jnp.float32)           # (tk, d)
        contrib = jnp.where(keep[:, None], contrib, 0.0)
        contrib = contrib * wgt[:, None]
        y = jnp.zeros((t, d), jnp.float32).at[tok].add(contrib)
        return (y.reshape(b_l, s_l, d).astype(compute_dtype),
                aux[None])

    seq_spec = ctx.seq
    x_spec = P(dp if dp else None, seq_spec, None)
    aux_spec = P(manual)                     # stack per-shard aux values
    y, aux = shard_map(
        local_moe, mesh=mesh,
        in_specs=(x_spec, P(), P(ep, None, None), P(ep, None, None),
                  P(ep, None, None)),
        out_specs=(x_spec, aux_spec),
        axis_names=set(manual),
        check_vma=False)(x, router_w, wg, wu, wd)
    return y, jnp.mean(aux)
