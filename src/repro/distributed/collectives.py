"""Collective helpers used by distributed serving / training paths.

The headline piece is :func:`seq_sharded_decode` -- flash-decoding adapted to
the ICI domain: the KV cache is sequence-sharded across the mesh, every device
computes a *partial* attention (numerator, logsumexp) over its shard, and the
partials are combined with a single small ``psum`` (two scalars + one vector
per head), instead of all-gathering the 100+ GB cache.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _partial_attention(q, k, v, scale):
    """q: (B,H,hd); k/v: (B,S_loc,KV,hd). Returns partial (o, lse) in fp32."""
    b, h, hd = q.shape
    kv = k.shape[2]
    groups = h // kv
    qg = q.reshape(b, kv, groups, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # scores: (B, KV, G, S_loc)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, kf) * scale
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    # normalised partial: the LSE-combine weights exp(lse_i - LSE) then sum
    # to exactly 1 across shards
    o = jnp.einsum("bkgs,bskd->bkgd", e, vf) / jnp.maximum(l, 1e-30)
    lse = (jnp.log(l) + m)[..., 0]           # (B,KV,G)
    return o, lse


def seq_sharded_decode(mesh: Mesh, kv_axes: Sequence[str]):
    """Build a shard_map'ed decode-attention over a KV cache whose sequence
    dim is sharded across ``kv_axes``.

    Returns fn(q (B,H,hd), k (B,S,KV,hd), v (B,S,KV,hd)) -> (B,H,hd).
    """
    axes = tuple(kv_axes)

    def local(q, k, v):
        scale = 1.0 / (q.shape[-1] ** 0.5)
        o, lse = _partial_attention(q, k, v, scale)
        # Combine partials across the sequence shards: softmax re-weighting.
        g_max = jax.lax.pmax(lse, axes)
        w = jnp.exp(lse - g_max)                      # (B,KV,G)
        num = jax.lax.psum(o * w[..., None], axes)
        den = jax.lax.psum(w, axes)
        out = num / den[..., None]
        b, kv, g, hd = out.shape
        return out.reshape(b, kv * g, hd)

    def fn(q, k, v):
        qspec = P(None, None, None)
        kvspec = P(None, axes if len(axes) > 1 else axes[0], None, None)
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(qspec, kvspec, kvspec),
            out_specs=qspec,
            check_vma=False,
        )(q, k, v)

    return fn


def psum_scatter_mean(x, axis_name: str):
    """reduce-scatter based mean (collective-friendly gradient averaging)."""
    n = jax.lax.psum(1, axis_name)
    return jax.lax.psum_scatter(x, axis_name, tiled=True) / n


@functools.partial(jax.jit, static_argnames=("axis",))
def interleave_halo(x, axis: int = 1):
    """Halo-exchange helper for spatially-partitioned convs (used in tests to
    validate XLA's own halo logic against a manual ring exchange)."""
    left = jnp.roll(x, 1, axis)
    right = jnp.roll(x, -1, axis)
    return jnp.concatenate([left, x, right], axis)
