"""Mixture-of-Experts layer with expert parallelism over the model axis.

Design (production pattern, validated against a dense-sum oracle):
  - Router + top-k run in plain jnp: activations are sharded over the batch
    axes and replicated over the model axis at this point, so the router is
    collective-free.
  - Dispatch / expert-compute / combine run under ``jax.shard_map`` manual
    over *only* the model axis (batch axes stay automatic). Each model rank
    owns E/tp experts, builds an (E_local, C) slot buffer by capacity
    scatter, runs the grouped SwiGLU matmuls on the MXU, gathers per-token
    results, and contributes a partial sum; a single ``psum`` over the model
    axis completes the combine — identical collective cost to a Megatron
    row-parallel matmul.
  - No all-to-all: tokens are replicated over the model axis between layers
    (Megatron TP convention), so expert parallelism only needs the final
    reduction. The trade-off (replicated activations vs. A2A dispatch) is
    recorded in DESIGN.md and revisited in EXPERIMENTS.md §Perf.

Capacity: C = ceil(cf * k * S / E) per sequence. Overflowed tokens fall into
a drop bin and contribute zero (standard capacity-factor semantics); the drop
fraction is returned as a metric.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.dist import DistContext
from repro.models.layers import glu_mlp


def capacity(cfg: ModelConfig, seq_len: int) -> int:
    c = int(cfg.capacity_factor * cfg.experts_per_token * seq_len
            / max(cfg.num_experts, 1)) + 1
    return max(8, -(-c // 8) * 8) if seq_len > 8 else max(1, c)


def router_topk(x: jax.Array, router_w: jax.Array, k: int):
    """x: (B,S,D) -> (top_vals (B,S,k) f32 renormalized, top_idx (B,S,k) i32,
    aux load-balance loss scalar)."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, k)
    top_vals = top_vals / jnp.maximum(
        jnp.sum(top_vals, axis=-1, keepdims=True), 1e-9)
    # Switch-style aux loss: E * sum_e f_e * P_e
    E = router_w.shape[-1]
    ass = jax.nn.one_hot(top_idx, E, dtype=jnp.float32).sum(axis=2)  # (B,S,E)
    f = jnp.mean(ass, axis=(0, 1)) / k
    p = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(f * p)
    return top_vals, top_idx, aux


def _dispatch_compute_combine(x, top_vals, top_idx, wg, wu, wd, *,
                              cap: int, e_offset, E_local: int, act: str):
    """Local-expert dispatch -> grouped SwiGLU -> gather-combine partial sum.

    x: (B,S,D); top_vals/top_idx: (B,S,K); wg/wu: (E_local,D,F); wd: (E_local,F,D).
    Returns (partial_out (B,S,D), dropped_frac scalar).
    """
    B, S, D = x.shape
    K = top_idx.shape[-1]
    local = (top_idx >= e_offset) & (top_idx < e_offset + E_local)
    li = jnp.where(local, top_idx - e_offset, E_local)  # E_local == overflow bin
    onehot = jax.nn.one_hot(li, E_local + 1, dtype=jnp.int32)  # (B,S,K,El+1)
    assign = onehot.sum(axis=2)  # (B,S,El+1)
    pos_before = jnp.cumsum(assign, axis=1) - assign
    slot = jnp.einsum("bske,bse->bsk", onehot, pos_before)  # (B,S,K)
    ok = local & (slot < cap)
    flat = jnp.where(ok, li * cap + slot, E_local * cap)
    b3 = jnp.arange(B)[:, None, None]
    buf_tok = jnp.full((B, E_local * cap + 1), S, jnp.int32)
    buf_tok = buf_tok.at[b3, flat].set(
        jnp.broadcast_to(jnp.arange(S)[None, :, None], (B, S, K)), mode="drop")
    buf_tok = buf_tok[:, : E_local * cap].reshape(B, E_local, cap)
    xpad = jnp.concatenate([x, jnp.zeros((B, 1, D), x.dtype)], axis=1)
    xe = xpad[b3[..., 0][:, :, None], buf_tok]  # (B,El,C,D)
    h = jnp.einsum("becd,edf->becf", xe, wg)
    u = jnp.einsum("becd,edf->becf", xe, wu)
    if act in ("silu", "swiglu"):
        h = jax.nn.silu(h)
    else:
        h = jax.nn.gelu(h, approximate=True)
    y = jnp.einsum("becf,efd->becd", h * u, wd)
    ypad = jnp.concatenate(
        [y.reshape(B, E_local * cap, D), jnp.zeros((B, 1, D), y.dtype)], axis=1)
    yk = ypad[b3[..., 0][:, :, None], flat]  # (B,S,K,D)
    w = jnp.where(ok, top_vals, 0.0).astype(yk.dtype)
    out = jnp.einsum("bsk,bskd->bsd", w, yk)
    dropped = jnp.mean((local & ~ok).astype(jnp.float32))
    return out, dropped


def moe_layer(
    x: jax.Array,
    router_w: jax.Array,
    wg: jax.Array,
    wu: jax.Array,
    wd: jax.Array,
    cfg: ModelConfig,
    dist: Optional[DistContext],
    shared: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full MoE layer. Returns (y, aux_loss, dropped_frac).

    wg/wu: (E, D, F); wd: (E, F, D) — sharded over E on the model axis.
    ``shared``: optional (wg, wu, wd) of the always-on shared-expert MLP.
    """
    E, K = cfg.num_experts, cfg.experts_per_token
    B, S, D = x.shape
    cap = capacity(cfg, S)
    top_vals, top_idx, aux = router_topk(x, router_w, K)
    top_vals = top_vals.astype(x.dtype)

    dp_ok = dist is not None and dist.mesh is not None \
        and B % dist.dp == 0
    if dist is not None and dist.manual_moe and E % dist.tp == 0 \
            and dist.tp > 1 and dp_ok:
        # FULL-manual shard_map (batch axes explicit too): the
        # partially-manual variant (auto batch axes) trips an XLA:CPU
        # partitioner CHECK ("Invalid binary instruction opcode copy") on
        # the dispatch scatter; full-manual sidesteps it and is also the
        # cheaper program (no auto-propagation through the scatter).
        E_local = E // dist.tp
        maxis = dist.model_axis
        P_ = jax.sharding.PartitionSpec
        spec_x = P_(dist.batch_axes, None, None)
        all_axes = tuple(dist.batch_axes) + (maxis,)
        n_all = dist.dp * dist.tp

        def inner(xl, tvl, til, wgl, wul, wdl):
            rank = jax.lax.axis_index(maxis)
            out, dropped = _dispatch_compute_combine(
                xl, tvl, til, wgl, wul, wdl,
                cap=cap, e_offset=rank * E_local, E_local=E_local, act=cfg.act)
            return (jax.lax.psum(out, maxis),
                    jax.lax.psum(dropped, all_axes) / n_all)

        y, dropped = jax.shard_map(
            inner,
            mesh=dist.mesh,
            in_specs=(spec_x, spec_x, spec_x,
                      P_(maxis), P_(maxis), P_(maxis)),
            out_specs=(spec_x, P_()),
            check_vma=False,
        )(x, top_vals, top_idx, wg, wu, wd)
    else:
        y, dropped = _dispatch_compute_combine(
            x, top_vals, top_idx, wg, wu, wd,
            cap=cap, e_offset=0, E_local=E, act=cfg.act)

    if shared is not None:
        sg, su, sd = shared
        y = y + glu_mlp(x, sg, su, sd, act=cfg.act)
    return y, aux, dropped
