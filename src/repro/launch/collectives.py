"""Posit-compressed gradient collectives (beyond-paper distributed trick).

``compressed_psum`` implements reduce-scatter + all-gather with both wire
phases carried as Posit(16,1) words after golden-zone re-centering: the
gradient tensor is scaled so its typical magnitude sits where p16e1 has
its 12-bit fraction (the paper's §5.1 scaling recommendation applied to
collectives).  Bytes on the wire: 2 x n x 2B vs f32 ring all-reduce's
2 x n x 4B — a 2x reduction on the cross-pod (slowest) links.

Used inside shard_map with manual axes ('pod', and optionally 'data');
the 'model' axis stays automatic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.policy import decode_tensor, encode_tensor

_GRAD_SCALE = 2.0 ** 8     # golden-zone re-centering for layer-norm'd grads


def compressed_psum(x: jax.Array, axis_name: str,
                    scale: float = _GRAD_SCALE) -> jax.Array:
    """Sum ``x`` across ``axis_name`` with p16e1-compressed wire traffic.

    reduce-scatter phase: all_to_all of encoded chunks, decode, local sum;
    all-gather phase: encoded own-chunk broadcast.  Mathematically the
    standard two-phase all-reduce; wire dtype int16.
    """
    p = jax.lax.axis_size(axis_name)
    orig_shape = x.shape
    orig_dtype = x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    pad = (-n) % p
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(p, -1)

    enc = encode_tensor(chunks * jnp.float32(scale), "p16e1")      # int16
    recv = jax.lax.all_to_all(enc, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)                          # (p, m)
    own = jnp.sum(decode_tensor(recv, "p16e1"), axis=0)             # (m,)
    enc2 = encode_tensor(own, "p16e1")
    full = jax.lax.all_gather(enc2, axis_name, tiled=False)         # (p, m)
    out = decode_tensor(full, "p16e1") * jnp.float32(1.0 / scale)
    out = out.reshape(-1)[:n].reshape(orig_shape)
    return out.astype(orig_dtype)


def compressed_psum_tree(tree, axis_name: str, min_size: int = 1 << 12):
    """Apply compressed_psum to large leaves; small leaves use plain psum
    (collective-launch overhead dominates below ~4K elements)."""
    def one(g):
        if g.size >= min_size and jnp.issubdtype(g.dtype, jnp.floating):
            return compressed_psum(g, axis_name)
        return jax.lax.psum(g, axis_name)
    return jax.tree.map(one, tree)


def limb_psum(limbs: jax.Array, nar: jax.Array, axis_name: str):
    """Cross-device quire reduction in LIMB space (repro.dist contract).

    ``limbs`` (..., L) int64 redundant radix-2^32 limbs from disjoint
    K slabs; ``nar`` (...) bool poison flags.  Integer limb adds are
    associative, so psum-ing the planes and rounding ONCE afterwards is
    bit-identical to accumulating the whole K range on one device — the
    reduction wire-format is exact by construction, unlike any float
    partial-sum scheme.  Headroom is unchanged: the psum reassociates the
    same K-term sum, so the K * 2^32 per-limb bound (DESIGN.md §6.1)
    already covers the merged state.  NaR ORs across devices (any NaR
    input poisons the fused op, per the standard).
    """
    limbs = jax.lax.psum(limbs, axis_name)
    nar = jax.lax.psum(jnp.asarray(nar, jnp.int32), axis_name) > 0
    return limbs, nar


_PIECE = 22      # bits per int32 wire piece of an int64 limb


def limb_psum_scatter(limbs: jax.Array, nar: jax.Array, axis_name: str,
                      scatter_dimension: int):
    """``psum_scatter`` of int64 limb planes (tiled) with int32 on the
    wire: a TPU cannot reduce-scatter int64.  Each limb travels as three
    int32 pieces, bits [0, 22), [22, 44) and the signed rest; summed over
    fewer than 2^9 devices none of them overflows int32, and the int64
    recombination is exact because the merged limbs keep the headroom
    ``limb_psum`` states.  NaR ORs across devices.
    """
    if jax.lax.axis_size(axis_name) >= 1 << (31 - _PIECE):
        raise ValueError(f"limb_psum_scatter: axis {axis_name!r} has too "
                         f"many devices for {_PIECE}-bit wire pieces")
    nl = limbs.shape[-1]
    mask = (1 << _PIECE) - 1
    pieces = jnp.concatenate([limbs & mask, (limbs >> _PIECE) & mask,
                              limbs >> 2 * _PIECE], axis=-1).astype(jnp.int32)
    pieces = jax.lax.psum_scatter(pieces, axis_name,
                                  scatter_dimension=scatter_dimension,
                                  tiled=True).astype(jnp.int64)
    limbs = (pieces[..., :nl] + (pieces[..., nl:2 * nl] << _PIECE)
             + (pieces[..., 2 * nl:] << 2 * _PIECE))
    nar = jax.lax.psum_scatter(jnp.asarray(nar, jnp.int32), axis_name,
                               scatter_dimension=scatter_dimension,
                               tiled=True) > 0
    return limbs, nar
