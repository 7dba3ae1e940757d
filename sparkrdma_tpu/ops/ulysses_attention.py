"""Ulysses-style sequence parallelism — the all-to-all SP schedule.

The second of the two sequence-parallel schedules (the first,
:mod:`ring_attention`, streams kv blocks around the ring). Ulysses
re-shards with two all-to-alls instead: heads are scattered and
sequence gathered, so each device computes FULL-sequence attention for
its subset of heads, then the output is re-sharded back to sequence.
One dense exchange each way — the same ``lax.all_to_all`` the shuffle
read path rides — versus the ring's E-1 neighbour hops; Ulysses wins
when head count ≥ shard count and the interconnect is all-to-all
capable (ICI), the ring when sequence is extreme or only neighbour
bandwidth is available.

Requires ``num_heads % num_shards == 0``. The per-device full-sequence
attention uses the Pallas flash kernel on TPU (interpreter off-TPU).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.ops.pallas_attention import flash_attention
from sparkrdma_tpu.parallel.mesh import make_mesh


def ulysses_shard_attention(q, k, v, axis: str, num_shards: int,
                            causal: bool = False, use_flash: bool = True):
    """The shard-local Ulysses schedule, for use INSIDE shard_map:
    seq-gather / head-scatter ([B, s, H, D] -> [B, s*E, H/E, D]) via
    one tiled ``all_to_all``, full-sequence attention per head group
    (the Pallas flash kernel — differentiable through its custom VJP),
    and the inverse exchange. Both :class:`UlyssesAttention` and the
    training step's sp schedule call this one implementation."""
    if num_shards > 1:
        q, k, v = (
            jax.lax.all_to_all(t, axis, split_axis=2, concat_axis=1,
                               tiled=True)
            for t in (q, k, v)
        )
    if use_flash:
        out = flash_attention(q, k, v, causal=causal)
    else:
        from sparkrdma_tpu.ops.ring_attention import reference_attention

        out = reference_attention(q, k, v, causal=causal)
    if num_shards > 1:
        out = jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=2,
                                 tiled=True)
    return out


class UlyssesAttention:
    """Compile-once all-to-all sequence-parallel attention."""

    def __init__(self, mesh: Optional[Mesh] = None, axis: Optional[str] = None):
        self.mesh = mesh if mesh is not None else make_mesh()
        if axis is None:
            axis = self.mesh.axis_names[-1]
        self.axis = axis
        self.num_shards = self.mesh.shape[axis]
        self._cache = {}

    def _build(self, shape, dtype, causal: bool, use_flash: bool):
        e = self.num_shards
        axis = self.axis
        spec = P(None, axis, None, None)  # sharded on sequence

        def shard_fn(q, k, v):
            return ulysses_shard_attention(
                q, k, v, axis, e, causal=causal, use_flash=use_flash
            )

        fn = shard_map(
            shard_fn,
            mesh=self.mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
        return jax.jit(fn)

    def __call__(self, q, k, v, causal: bool = False, use_flash: bool = True):
        b, s, h, d = q.shape
        if h % self.num_shards:
            raise ValueError(
                f"num_heads {h} must divide by shard count {self.num_shards}"
            )
        key = (q.shape, jnp.dtype(q.dtype).name, causal, use_flash)
        fn = self._cache.get(key)
        if fn is None:
            fn = self._build(q.shape, q.dtype, causal, use_flash)
            self._cache[key] = fn
        sharding = NamedSharding(self.mesh, P(None, self.axis, None, None))
        q = jax.device_put(q, sharding)
        k = jax.device_put(k, sharding)
        v = jax.device_put(v, sharding)
        return fn(q, k, v)
