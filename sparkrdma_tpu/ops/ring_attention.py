"""Ring attention — sequence-parallel long-context attention over the mesh.

The framework's sequence/context-parallel capability (build brief:
long-context is first-class; the reference's analogous *mechanism* is
chunked block aggregation for objects larger than one buffer,
SURVEY.md §2.3 / §5.1 #7). Sequence is sharded over the ``exec`` axis;
each device holds one query block and streams every peer's key/value
block through the same neighbour-ring schedule as
:meth:`ExchangeProgram.ring_exchange` — one block in flight per hop,
only ICI-neighbour links used.

Numerics: blockwise online softmax (flash-attention style running
max / denominator), so the result is exact attention — not an
approximation — with O(seq/E) memory per device.

Layout: ``[batch, seq, heads, head_dim]`` global, sharded on ``seq``.
Compile-once per (mesh, shapes, causal) via :class:`RingAttention`.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.parallel.mesh import make_mesh

NEG_INF = -1e30


def _block_attn(q, k, v, mask, m_prev, num_prev, den_prev):
    """One blockwise online-softmax accumulation step.

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; mask: [Sq, Sk] additive.
    Carries: m (running max) [B, H, Sq], num [B, Sq, H, D], den [B, H, Sq].
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    # scores in fp32 for stable softmax regardless of input dtype
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale + mask[None, None, :, :]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    # renormalize previous accumulator to the new max
    correction = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])  # [B, H, Sq, Sk]
    num = num_prev * correction.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v.astype(jnp.float32)
    )
    den = den_prev * correction + p.sum(axis=-1)
    return m_new, num, den


class RingAttention:
    """Compile-once exact ring attention over a 1-D mesh axis."""

    def __init__(self, mesh: Optional[Mesh] = None, axis: Optional[str] = None):
        self.mesh = mesh if mesh is not None else make_mesh()
        if axis is None:
            axis = self.mesh.axis_names[-1]  # exec (ICI) by default
        self.axis = axis
        self.num_shards = self.mesh.shape[axis]
        self._cache = {}

    def _build(self, shape, dtype, causal: bool):
        e = self.num_shards
        axis = self.axis
        # shard sequence (dim 1); replicate everything else
        spec = P(None, axis, None, None)

        def shard_fn(q, k, v):
            b, s_loc, h, d = q.shape
            me = jax.lax.axis_index(axis)
            perm = [(i, (i + 1) % e) for i in range(e)]

            m = jnp.full((b, h, s_loc), NEG_INF, dtype=jnp.float32)
            num = jnp.zeros((b, s_loc, h, d), dtype=jnp.float32)
            den = jnp.zeros((b, h, s_loc), dtype=jnp.float32)

            k_blk, v_blk = k, v
            q_pos = me * s_loc + jnp.arange(s_loc)
            for hop in range(e):
                src = (me - hop) % e  # which shard's kv block we hold now
                if causal:
                    kv_pos = src * s_loc + jnp.arange(s_loc)
                    mask = jnp.where(
                        q_pos[:, None] >= kv_pos[None, :], 0.0, NEG_INF
                    ).astype(jnp.float32)
                else:
                    mask = jnp.zeros((s_loc, s_loc), dtype=jnp.float32)
                m, num, den = _block_attn(q, k_blk, v_blk, mask, m, num, den)
                if hop != e - 1:
                    # one kv block in flight per device per hop — the
                    # ring_exchange schedule (neighbour links only)
                    k_blk = jax.lax.ppermute(k_blk, axis, perm)
                    v_blk = jax.lax.ppermute(v_blk, axis, perm)

            out = num / den.transpose(0, 2, 1)[..., None]
            return out.astype(q.dtype)

        fn = shard_map(
            shard_fn,
            mesh=self.mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
        return jax.jit(fn)

    def __call__(self, q, k, v, causal: bool = False):
        """Exact attention over globally [B, S, H, D] inputs sharded on S."""
        key = (q.shape, jnp.dtype(q.dtype).name, causal)
        fn = self._cache.get(key)
        if fn is None:
            fn = self._build(q.shape, q.dtype, causal)
            self._cache[key] = fn
        sharding = NamedSharding(self.mesh, P(None, self.axis, None, None))
        q = jax.device_put(q, sharding)
        k = jax.device_put(k, sharding)
        v = jax.device_put(v, sharding)
        return fn(q, k, v)


def reference_attention(q, k, v, causal: bool = False):
    """Dense single-device attention for correctness checks."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        n = q.shape[1]
        mask = jnp.where(
            jnp.arange(n)[:, None] >= jnp.arange(n)[None, :], 0.0, NEG_INF
        )
        s = s + mask[None, None]
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)
