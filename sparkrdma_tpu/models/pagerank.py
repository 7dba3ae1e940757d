"""Device-resident distributed PageRank — the multi-round all-to-all workload.

BASELINE.md workload #5 (GraphX PageRank on twitter-2010: "multi-round
all-to-all"). The reference would run this as one Spark shuffle per
iteration; here every iteration is a single jitted SPMD step whose
exchange is one ``lax.all_to_all`` over the mesh — the same collective
the shuffle read path rides, exercised iteratively.

Layout: vertices dense-sharded over the mesh ([E, n_local] ranks).
Edges are preprocessed host-side into per-(src-shard → dst-shard)
padded blocks, so each shard scatter-adds its out-contributions into E
destination-shard vectors (static shapes), exchanges them, and sums
what it receives:

  contrib[d] = Σ_{(s→t) edges to shard d} rank[s] / outdeg[s]
  rank' = (1-α)/N + α · (Σ_src received contrib + dangling share)

The whole power iteration runs in ONE jit (``lax.fori_loop`` with the
collective inside) — compile-once / iterate-many.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding

from sparkrdma_tpu.parallel.mesh import make_mesh, shard_spec


class PageRank:
    def __init__(self, mesh: Optional[Mesh] = None, damping: float = 0.85):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.num_shards = math.prod(self.mesh.shape.values())
        self.damping = damping
        self._cache = {}

    # ------------------------------------------------------------------
    def prepare(self, edges: np.ndarray, num_vertices: int):
        """Host-side preprocessing: pad per-(src,dst)-shard edge blocks.

        ``edges``: [m, 2] int array of (src, dst). Vertices are
        block-partitioned: vertex v lives on shard v // n_local.
        Returns arrays ready for :meth:`run`.
        """
        e = self.num_shards
        n_local = int(math.ceil(num_vertices / e))
        src, dst = edges[:, 0], edges[:, 1]
        outdeg = np.bincount(src, minlength=num_vertices).astype(np.float32)
        s_shard, d_shard = src // n_local, dst // n_local
        # bucket edges by (src shard, dst shard)
        cap = 0
        buckets = {}
        for i in range(e):
            for j in range(e):
                sel = (s_shard == i) & (d_shard == j)
                blk = edges[sel]
                buckets[(i, j)] = blk
                cap = max(cap, len(blk))
        cap = max(cap, 1)
        # padded local-index blocks: [E_src, E_dst, cap, 2], -1 = padding
        packed = np.full((e, e, cap, 2), -1, dtype=np.int32)
        for (i, j), blk in buckets.items():
            if len(blk):
                packed[i, j, : len(blk), 0] = blk[:, 0] % n_local
                packed[i, j, : len(blk), 1] = blk[:, 1] % n_local
        deg = np.zeros((e * n_local,), dtype=np.float32)
        deg[:num_vertices] = outdeg
        return packed, deg, n_local

    # ------------------------------------------------------------------
    def _build(self, n_local: int, cap: int, iters: int, num_vertices: int):
        axes = tuple(self.mesh.axis_names)
        spec = shard_spec(self.mesh)
        alpha = self.damping

        def shard_fn(rank, deg, valid, blocks):
            # rank/deg/valid: [n_local]; blocks: [E_dst, cap, 2] local
            # indices. ``valid`` masks the padding slots that exist only
            # because num_vertices does not divide the shard count —
            # they must hold zero rank and shed no dangling mass.
            safe_deg = jnp.maximum(deg, 1.0)

            def one_iter(_, r):
                outc = jnp.where(deg > 0, r / safe_deg, 0.0)
                # dangling mass is redistributed uniformly (standard PR)
                dangling = jax.lax.psum(
                    jnp.where((deg == 0) & (valid > 0), r, 0.0).sum(), axes
                )

                def contrib_for(blk):
                    s_idx, d_idx = blk[:, 0], blk[:, 1]
                    valid = s_idx >= 0
                    vals = jnp.where(valid, outc[jnp.maximum(s_idx, 0)], 0.0)
                    return jnp.zeros((n_local,), jnp.float32).at[
                        jnp.maximum(d_idx, 0)
                    ].add(vals, mode="drop")

                contribs = jax.vmap(contrib_for)(blocks)  # [E_dst, n_local]
                # one all_to_all per iteration: row d -> shard d
                recv = jax.lax.all_to_all(
                    contribs, axes, split_axis=0, concat_axis=0, tiled=True
                )
                inflow = recv.sum(axis=0)
                r_new = (1.0 - alpha) / num_vertices + alpha * (
                    inflow + dangling / num_vertices
                )
                return jnp.where(valid > 0, r_new, 0.0)

            return jax.lax.fori_loop(0, iters, one_iter, rank)

        fn = shard_map(
            shard_fn,
            mesh=self.mesh,
            in_specs=(spec, spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
        return jax.jit(fn)

    # ------------------------------------------------------------------
    def run(
        self, edges: np.ndarray, num_vertices: int, iters: int = 20
    ) -> np.ndarray:
        packed, deg, n_local = self.prepare(edges, num_vertices)
        e = self.num_shards
        cap = packed.shape[2]
        key = (n_local, cap, iters, num_vertices)
        fn = self._cache.get(key)
        if fn is None:
            fn = self._build(n_local, cap, iters, num_vertices)
            self._cache[key] = fn
        sharding = NamedSharding(self.mesh, shard_spec(self.mesh))
        r0 = np.zeros((e * n_local,), dtype=np.float32)
        r0[:num_vertices] = 1.0 / num_vertices
        valid = np.zeros((e * n_local,), dtype=np.float32)
        valid[:num_vertices] = 1.0
        rank0 = jax.device_put(r0, sharding)
        deg_d = jax.device_put(deg, sharding)
        valid_d = jax.device_put(valid, sharding)
        blocks = jax.device_put(
            packed.reshape(e * e, cap, 2),
            NamedSharding(self.mesh, shard_spec(self.mesh)),
        )
        out = fn(rank0, deg_d, valid_d, blocks)
        return np.asarray(out)[:num_vertices]


def reference_pagerank(
    edges: np.ndarray, num_vertices: int, iters: int = 20, damping: float = 0.85
) -> np.ndarray:
    """Dense numpy power iteration for correctness checks."""
    rank = np.full((num_vertices,), 1.0 / num_vertices, dtype=np.float64)
    outdeg = np.bincount(edges[:, 0], minlength=num_vertices).astype(np.float64)
    for _ in range(iters):
        contrib = np.zeros(num_vertices, dtype=np.float64)
        outc = np.divide(rank, outdeg, out=np.zeros_like(rank), where=outdeg > 0)
        np.add.at(contrib, edges[:, 1], outc[edges[:, 0]])
        dangling = rank[outdeg == 0].sum()
        rank = (1 - damping) / num_vertices + damping * (
            contrib + dangling / num_vertices
        )
    return rank.astype(np.float32)
