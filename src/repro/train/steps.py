"""jit-able step functions: train_step / prefill_step / decode_step.

Factories close over (cfg, mesh, parallel, train-config) and return functions
suitable for ``jax.jit`` with explicit in/out shardings — the same objects are
used by the real trainer, the serving loop and the multi-pod dry-run.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig, TrainConfig
from repro.distributed.sharding import make_shard_ctx, mesh_axes
from repro.models import transformer as T
from repro.optim import adamw, compress_psum

AUX_WEIGHT = 0.01      # MoE load-balance loss weight


def _positions(tokens):
    B, S = tokens.shape
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))


def loss_fn(params, batch: Dict[str, Any], cfg: ModelConfig, ctx=None,
            remat_policy: str = "none"):
    h, aux = T.forward(params, batch["tokens"], _positions(batch["tokens"]),
                       cfg, embeds=batch.get("embeds"),
                       frames=batch.get("frames"), ctx=ctx,
                       remat_policy=remat_policy)
    loss = T.chunked_xent(params, h, batch["labels"], cfg,
                          mask=batch.get("mask"))
    total = loss + AUX_WEIGHT * aux
    return total, {"loss": loss, "aux": aux}


def make_train_step(cfg: ModelConfig, mesh, parallel: ParallelConfig,
                    tc: TrainConfig):
    ctx = make_shard_ctx(mesh, parallel)
    _, _, multi_pod = mesh_axes(mesh)
    compress = parallel.grad_compression
    grad_fn = jax.value_and_grad(
        partial(loss_fn, cfg=cfg, ctx=ctx,
                remat_policy=parallel.remat_policy), has_aux=True)

    def compute_grads(params, batch):
        if parallel.microbatches > 1:
            mb = parallel.microbatches

            def mb_slice(x):
                B = x.shape[0]
                return x.reshape((mb, B // mb) + x.shape[1:])

            mb_batch = {k: mb_slice(v) for k, v in batch.items()}

            adt = jnp.dtype(parallel.accum_dtype)

            def body(acc, mbatch):
                (l, m), g = grad_fn(params, mbatch)
                acc_g, acc_l = acc
                acc_g = jax.tree.map(
                    lambda a, b: (a.astype(jnp.float32)
                                  + b.astype(jnp.float32) / mb).astype(adt),
                    acc_g, g)
                return (acc_g, acc_l + l / mb), m

            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, adt), params)
            (grads, loss), metrics = jax.lax.scan(
                body, (zero_g, jnp.zeros((), jnp.float32)), mb_batch)
            metrics = jax.tree.map(lambda x: x.mean(), metrics)
            return loss, metrics, grads
        (loss, metrics), grads = grad_fn(params, batch)
        return loss, metrics, grads

    def train_step(params, opt_state, batch):
        loss, metrics, grads = compute_grads(params, batch)
        new_params, new_state, stats = adamw.update(grads, opt_state, params, tc)
        metrics = dict(metrics, **stats, total_loss=loss)
        return new_params, new_state, metrics

    if compress != "none" and multi_pod:
        # pod-local grads + explicit compressed cross-pod reduce.
        # shard_map over "pod" only; data/model stay under GSPMD (auto axes).
        from jax.sharding import PartitionSpec as P

        def train_step_compressed(params, opt_state, batch):
            def pod_body(params, opt_state, batch):
                loss, metrics, grads = compute_grads(params, batch)
                grads = compress_psum(grads, "pod", compress)
                loss = jax.lax.pmean(loss, "pod")
                metrics = jax.tree.map(lambda x: jax.lax.pmean(x, "pod"),
                                       metrics)
                new_params, new_state, stats = adamw.update(
                    grads, opt_state, params, tc)
                return new_params, new_state, dict(metrics, **stats,
                                                   total_loss=loss)

            pspec = jax.tree.map(lambda _: P(), params)
            ospec = jax.tree.map(lambda _: P(), opt_state)
            bspec = {k: P("pod") for k in batch}
            return jax.shard_map(
                pod_body, mesh=mesh,
                in_specs=(pspec, ospec, bspec),
                out_specs=(pspec, ospec,
                           jax.tree.map(lambda _: P(),
                                        {"loss": 0, "aux": 0, "grad_norm": 0,
                                         "lr": 0, "total_loss": 0})),
                check_vma=False,
                axis_names={"pod"})(params, opt_state, batch)

        return train_step_compressed

    return train_step


def make_prefill_step(cfg: ModelConfig, mesh, parallel: ParallelConfig,
                      shape: ShapeConfig, kv_layout: str = "bksd"):
    ctx = make_shard_ctx(mesh, parallel)

    def prefill_step(params, batch):
        out = T.prefill(params, batch["tokens"], cfg, max_len=shape.seq_len,
                        kv_layout=kv_layout, embeds=batch.get("embeds"),
                        frames=batch.get("frames"), ctx=ctx,
                        kv_window=parallel.window_kv_cache)
        logits, cache, cross = out
        if cross is None:
            return logits, cache
        return logits, cache, cross

    return prefill_step


def make_decode_step(cfg: ModelConfig, mesh, parallel: ParallelConfig,
                     kv_layout: str = "bksd", with_cross: bool = False):
    ctx = make_shard_ctx(mesh, parallel, for_decode=True)
    _, tp, _ = mesh_axes(mesh)
    # sharding-aware cache-write selection (see layers._cache_write_masked):
    # head-sharded cache -> cheap DUS; sequence-sharded cache -> masked select
    kv_update = "dus" if cfg.num_kv_heads % mesh.shape[tp] == 0 else "masked"

    if with_cross:
        def decode_step(params, cache, token, cache_len, cross):
            return T.decode_step(params, cache, token, cache_len, cfg,
                                 kv_layout=kv_layout, cross=cross, ctx=ctx,
                                 kv_update=kv_update,
                                 kv_window=parallel.window_kv_cache)
        return decode_step

    def decode_step(params, cache, token, cache_len):
        return T.decode_step(params, cache, token, cache_len, cfg,
                             kv_layout=kv_layout, ctx=ctx,
                             kv_update=kv_update,
                             kv_window=parallel.window_kv_cache)

    return decode_step
