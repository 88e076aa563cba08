"""Step builders: train_step (fwd+bwd+AdamW), prefill_step, serve_step.

``make_train_step``    — jit auto-parallel (XLA inserts all collectives).
``make_train_step_compressed`` — shard_map with manual DP axes: gradients
are synced by the posit16-compressed two-phase all-reduce from
launch/collectives.py; the 'model' axis stays automatic.  This is the
paper-aligned distributed-optimization variant (§Perf compares both).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.launch import context as dist_ctx
from repro.launch.collectives import compressed_psum_tree
from repro.launch.mesh import dp_axes
from repro.models.common import ArchConfig
from repro.models.lm import forward_prefill, forward_train, serve_step
from repro.optim import adamw_update


def _cast_params(params, dtype):
    """One f32->compute-dtype cast per step on the SHARDED masters, so FSDP
    all-gathers move compute-dtype bytes (gather-then-convert would move
    f32; observed as 3.25 GiB f32 weight gathers on llama3-405b)."""
    def cast(w):
        if hasattr(w, "dtype") and w.dtype == jnp.float32:
            return w.astype(dtype)
        return w
    return jax.tree.map(cast, params)


def make_train_step(cfg: ArchConfig, *, remat: bool = True, lr: float = 3e-4,
                    dist=None):
    compress_moments = cfg.get_policy().opt_compression is not None
    compute_dtype = jnp.dtype(cfg.get_policy().compute_dtype)

    def train_step(params, opt_state, batch):
        with dist_ctx.use(dist):
            def loss_fn(pc):
                loss, metrics = forward_train(pc, batch, cfg, remat=remat)
                return loss, metrics
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(_cast_params(params, compute_dtype))
        params2, opt2, gnorm = adamw_update(
            params, opt_state, grads, lr=lr,
            compress_moments=compress_moments)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        return params2, opt2, metrics

    return train_step


def make_train_step_compressed(cfg: ArchConfig, mesh, *, remat: bool = True,
                               lr: float = 3e-4, dist=None):
    """Manual-DP variant: per-DP-shard fwd/bwd, then posit16-compressed
    gradient all-reduce across the DP axes ('pod' first — the slow links).
    """
    compress_moments = cfg.get_policy().opt_compression is not None
    dp = dp_axes(mesh)

    def per_shard(params, opt_state, batch):
        def loss_fn(p):
            loss, metrics = forward_train(p, batch, cfg, remat=remat)
            return loss, metrics
        with dist_ctx.use(None):   # inside manual DP: MoE uses local path
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
        # average across DP shards with compressed wire traffic
        for ax in dp:
            grads = compressed_psum_tree(grads, ax)
        dp_size = 1
        for ax in dp:
            dp_size *= jax.lax.axis_size(ax)
        grads = jax.tree.map(lambda g: g / dp_size, grads)
        params2, opt2, gnorm = adamw_update(
            params, opt_state, grads, lr=lr,
            compress_moments=compress_moments)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, dp), metrics)
        return params2, opt2, metrics

    def train_step(params, opt_state, batch):
        # params/opt replicated over DP (model-axis sharding stays auto);
        # batch split over DP on its leading dim.
        f = shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(), P(), P(dp)),
            out_specs=(P(), P(), P()),
            axis_names=set(dp),
            check_vma=False)
        return f(params, opt_state, batch)

    return train_step


def make_prefill_step(cfg: ArchConfig, dist=None):
    def prefill_step(params, batch):
        with dist_ctx.use(dist):
            return forward_prefill(params, batch, cfg)
    return prefill_step


def make_serve_step(cfg: ArchConfig, dist=None):
    def step(params, cache, tokens, pos):
        with dist_ctx.use(dist):
            return serve_step(params, cache, tokens, pos, cfg)
    return step
