"""Ring attention: sequence/context parallelism over the ICI ring.

Long-context support is first-class in this framework: sequences too long
for one device's HBM are sharded across the mesh's ``data`` axis, and
attention runs blockwise with K/V shards rotating around the ring via
``ppermute`` while a running log-sum-exp keeps the softmax stable
(the standard ring-attention recipe; no reference analogue — the reference
has no compute plane, SURVEY.md 5.7).

Shapes (per device, inside ``shard_map``): q/k/v ``[B, T_local, H, D]``.
The full sequence is ``T_local * axis_size``. Causal masking uses global
block offsets so device i attends correctly to rotated shards.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _block_attention(q, k, v, *, bias=None, scale: float):
    """Plain attention scores for one (q-block, kv-block) pair; returns
    (unnormalized out, running max, running denom) pieces."""
    # [B, H, Tq, Tk]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if bias is not None:
        scores = scores + bias
    m = jnp.max(scores, axis=-1, keepdims=True)  # [B,H,Tq,1]
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return o, m, l


def _causal_bias(t_q: int, t_k: int, q_offset, k_offset, dtype):
    """Bias masking keys that are in the future of each query, with global
    offsets (shards are rotated, so local indices are not global)."""
    q_idx = q_offset + jax.lax.broadcasted_iota(jnp.int32, (t_q, t_k), 0)
    k_idx = k_offset + jax.lax.broadcasted_iota(jnp.int32, (t_q, t_k), 1)
    mask = k_idx > q_idx
    return jnp.where(mask, jnp.asarray(-1e9, dtype=dtype), 0).astype(dtype)


def ring_attention_local(q, k, v, *, axis_name: str, causal: bool = True):
    """Per-device body (call inside shard_map over ``axis_name``)."""
    axis_size = jax.lax.psum(1, axis_name)
    my_index = jax.lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    q_offset = my_index * t_local

    def step(carry, i):
        o_acc, m_acc, l_acc, k_cur, v_cur = carry
        # which shard are we holding? (rotations move shard s to s-1)
        src_index = (my_index + i) % axis_size
        k_offset = src_index * t_local
        bias = None
        if causal:
            bias = _causal_bias(t_local, t_local, q_offset, k_offset,
                                jnp.float32)[None, None]
        o, m, l = _block_attention(q, k_cur, v_cur, bias=bias, scale=scale)
        # merge with running (log-sum-exp) accumulators
        m_new = jnp.maximum(m_acc, m)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m - m_new)
        o_acc = o_acc * alpha.transpose(0, 2, 1, 3) + o * beta.transpose(0, 2, 1, 3)
        l_acc = l_acc * alpha + l * beta
        # rotate K/V around the ring for the next step
        perm = [(j, (j - 1) % axis_size) for j in range(axis_size)]
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o_acc, m_new, l_acc, k_next, v_next), None

    o0 = jnp.zeros((b, t_local, h, d), dtype=jnp.float32)
    m0 = jnp.full((b, h, t_local, 1), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, t_local, 1), dtype=jnp.float32)
    (o, m, l, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k.astype(jnp.float32), v.astype(jnp.float32)),
        jnp.arange(axis_size))
    out = o / jnp.maximum(l, 1e-20).transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


def ring_attention(q, k, v, *, mesh, axis: str = "data",
                   causal: bool = True):
    """Sequence-parallel attention: q/k/v sharded on ``axis`` along T.

    Global shapes ``[B, T, H, D]``; per-device compute is blockwise with
    K/V rotating over ICI. XLA overlaps each ppermute with the next
    block's einsums.
    """
    spec = P(None, axis, None, None)
    body = functools.partial(ring_attention_local, axis_name=axis,
                             causal=causal)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


def reference_attention(q, k, v, *, causal: bool = True):
    """Single-device attention for correctness checks."""
    b, t, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk",
                        q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        bias = _causal_bias(t, t, 0, 0, jnp.float32)
        scores = scores + bias[None, None]
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
