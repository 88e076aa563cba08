"""Shared model substrate: ArchConfig, numeric-policy-aware Linear, norms,
rotary embeddings, initializers.

Functional style: every module is (init(key, ...) -> params, apply(params,
x, ...) -> y) over plain dict pytrees, with explicit dtypes everywhere
(x64 is globally enabled for the posit core, so nothing may rely on dtype
defaults).  Each param leaf carries a logical-axis annotation consumed by
``repro.launch.sharding``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import Policy, get_policy


# --------------------------------------------------------------------------
# configuration
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    act: str = "silu"
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    # local/global attention pattern (gemma3: 5 local : 1 global)
    local_window: int = 0
    local_ratio: int = 0
    # MoE
    n_experts: int = 0
    top_k: int = 0
    # SSM (Mamba2/SSD)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_head_dim: int = 64
    hybrid_attn_every: int = 0     # zamba2: shared attn block cadence
    # encoder-decoder (whisper)
    enc_layers: int = 0
    enc_seq: int = 0
    # VLM stub frontend
    vis_tokens: int = 0
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    policy: str = "bf16"

    @property
    def d_q(self) -> int:
        return self.n_heads * self.d_head

    @property
    def d_kv(self) -> int:
        return self.n_kv_heads * self.d_head

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def layer_kinds(self) -> list[str]:
        """Per-layer block kind for the decoder stack."""
        kinds = []
        for i in range(self.n_layers):
            if self.family in ("ssm", "hybrid"):
                kinds.append("ssm")
            elif self.local_ratio and (i + 1) % (self.local_ratio + 1) != 0:
                kinds.append("local")
            else:
                kinds.append("attn")
        return kinds

    def get_policy(self) -> Policy:
        return get_policy(self.policy)

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for the long_500k shape (DESIGN.md §4)."""
        return self.family in ("ssm", "hybrid") or (
            self.local_ratio > 0 and self.local_window > 0)


# --------------------------------------------------------------------------
# param helpers
# --------------------------------------------------------------------------

class Axes(tuple):
    """Logical-axis annotation that travels inside the param pytree but has
    NO JAX leaves (registered with the names as static aux data), so grad /
    optimizer tree-maps pass straight through it."""


jax.tree_util.register_pytree_node(
    Axes, lambda a: ((), tuple(a)), lambda aux, _: Axes(aux))


def param(key, shape, axes: Sequence[Optional[str]], scale: float = 1.0,
          dtype=jnp.float32, init: str = "normal"):
    """A param leaf + its logical sharding axes (stored side-by-side)."""
    if init == "normal":
        fan_in = shape[0] if len(shape) > 1 else shape[-1]
        std = scale / np.sqrt(fan_in)
        w = jax.random.normal(key, shape, dtype=jnp.float32) * std
    elif init == "zeros":
        w = jnp.zeros(shape, jnp.float32)
    elif init == "ones":
        w = jnp.ones(shape, jnp.float32)
    else:
        raise ValueError(init)
    return {"w": w.astype(dtype), "axes": Axes(axes)}


def leaf(p):
    if "qw" in p:          # posit-quantized leaf (serving.quantize)
        from repro.serving.quantize import dequant_leaf
        return dequant_leaf(p)
    return p["w"]


def is_param(x) -> bool:
    return isinstance(x, dict) and set(x) == {"w", "axes"}


def map_params(fn, tree):
    """Map fn(leaf_dict) over all param leaves of a model pytree."""
    if is_param(tree):
        return fn(tree)
    if isinstance(tree, dict):
        return {k: map_params(fn, v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(map_params(fn, v) for v in tree)
    return tree


# --------------------------------------------------------------------------
# basic layers
# --------------------------------------------------------------------------

def rmsnorm_init(key, d, axes=("embed",)):
    return {"scale": param(key, (d,), axes, init="ones")}


def rmsnorm(params, x, eps):
    """f32 only in the reduction: x-shaped f32 elementwise intermediates
    inside checkpointed scan bodies get stacked as f32 residuals by the
    scan linearizer (verified minimal repro; EXPERIMENTS.md §Perf), so the
    normalize/scale multiplies stay in the compute dtype."""
    dt = x.dtype
    var = (jnp.einsum("...d,...d->...", x, x,
                      preferred_element_type=jnp.float32)
           / x.shape[-1])[..., None]
    r = jax.lax.rsqrt(var + jnp.float32(eps)).astype(dt)
    return x * r * leaf(params["scale"]).astype(dt)


def linear_init(key, d_in, d_out, axes, bias=False, scale=1.0):
    p = {"w": param(key, (d_in, d_out), axes, scale=scale)}
    if bias:
        p["b"] = param(key, (d_out,), (axes[-1],), init="zeros")
    return p


def linear(params, x, policy: Policy, compute_dtype):
    """Policy-aware dense layer — the paper's technique enters here: with a
    posit policy, weights and activations are rounded to the Posit(32,2)
    lattice (simulated quantization; the Pallas kernel is the native
    execution of the same semantics on TPU)."""
    if "qw" in params["w"]:
        # posit-quantized weight leaf (serving.quantize): the words are
        # decoded inside the jit (xla backend) or consumed directly by
        # the Pallas GEMM (pallas backend); the per-channel pow2 scale
        # is folded into the output exactly.  Policy weight/activation
        # quantization does not stack on top — the leaf IS the lattice.
        from repro.serving.quantize import quant_matmul
        y = quant_matmul(x, params["w"], compute_dtype)
        if "b" in params:
            y = y + leaf(params["b"]).astype(compute_dtype)
        return y
    w = leaf(params["w"])
    w = policy.maybe_quantize_weights(w)
    x = policy.maybe_quantize_acts(x)
    # Train: output in compute dtype (the MXU accumulates bf16 dots in f32
    # internally; an f32 *output* becomes a stacked f32 scan residual).
    # Decode: f32 outputs (see DistContext.f32_partials).
    from repro.launch import context as dist_ctx
    ctx = dist_ctx.current()
    pref = jnp.float32 if (ctx is not None and ctx.f32_partials) \
        else compute_dtype
    y = jnp.dot(x.astype(compute_dtype), w.astype(compute_dtype),
                preferred_element_type=pref).astype(compute_dtype)
    if "b" in params:
        y = y + leaf(params["b"]).astype(compute_dtype)
    return y


def embed_init(key, vocab, d):
    return {"table": param(key, (vocab, d), ("vocab", "embed"), scale=1.0)}


def embed(params, ids, compute_dtype):
    """Vocab-parallel embedding lookup.

    With a vocab-sharded table, a plain gather makes SPMD replicate the
    indices AND the (tokens, d) output on every vocab shard; the standard
    fix (Megatron vocab-parallel embedding) is a local masked gather +
    psum, which needs manual sharding — done here with shard_map over the
    'model' axis when a distribution context is active."""
    from repro.launch import context as dist_ctx
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    table = leaf(params["table"])
    ctx = dist_ctx.current()
    n_sh = ctx.mesh.shape.get("model", 1) if ctx is not None else 1
    if ctx is None or n_sh == 1 or table.shape[0] % n_sh:
        return jnp.take(table.astype(compute_dtype), ids, axis=0)

    v_local = table.shape[0] // n_sh
    # vocab and sequence share the 'model' axis: gather locally against the
    # full (model-replicated) id list, then reduce-scatter the partial sums
    # over the sequence dim (Megatron sequence-parallel embedding)
    seq_shard = ctx.seq is not None and ids.shape[1] % n_sh == 0

    def local_lookup(tab, ids_l):
        shard = jax.lax.axis_index("model")
        adj = ids_l - shard * v_local
        valid = (adj >= 0) & (adj < v_local)
        g = jnp.take(tab.astype(compute_dtype),
                     jnp.clip(adj, 0, v_local - 1), axis=0)
        g = jnp.where(valid[..., None], g,
                      jnp.zeros((), compute_dtype))
        # psum in f32: XLA CPU's AllReducePromotion CHECK-fails when it
        # clones the copy-rooted reducer a bf16 psum gets (bisected during
        # the dry-run; see EXPERIMENTS.md §Perf)
        g = g.astype(jnp.float32)
        if seq_shard:
            out = jax.lax.psum_scatter(g, "model", scatter_dimension=1,
                                       tiled=True)
        else:
            out = jax.lax.psum(g, "model")
        return out.astype(compute_dtype)

    dp_spec = ctx.dp if ctx.dp else None
    out = shard_map(
        local_lookup, mesh=ctx.mesh,
        in_specs=(P("model", None), P(dp_spec, None)),
        out_specs=P(dp_spec, "model" if seq_shard else None, None),
        axis_names={"model"} | set(ctx.dp),
        check_vma=False)(table, ids)
    return out


def unembed(params, x, compute_dtype):
    t = leaf(params["table"]).astype(compute_dtype)
    return jnp.dot(x, t.T, preferred_element_type=jnp.float32)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


# --------------------------------------------------------------------------
# rotary embeddings
# --------------------------------------------------------------------------

def rope(x, positions, theta: float):
    """x: (..., S, H, Dh); positions: (..., S) int32."""
    dh = x.shape[-1]
    half = dh // 2
    freq = (jnp.float32(theta)
            ** -(jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freq        # (..., S, half)
    cos = jnp.cos(ang)[..., None, :].astype(x.dtype)   # (S,1,half): small
    sin = jnp.sin(ang)[..., None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
