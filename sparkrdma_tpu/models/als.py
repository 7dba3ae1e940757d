"""Device-resident ALS matrix factorization — the iterative wide shuffle.

BASELINE.md workload #4 (MLlib ALS on MovieLens-20M). In Spark each
half-iteration is a wide shuffle carrying the other side's factor
blocks to every partition; here that exchange is one ``all_gather``
over the mesh per half-iteration (factors ride ICI), and the per-row
normal-equation solves are batched dense ops on the MXU
(``vmap``-batched Cholesky-style solves over static padded rating
lists).

Layout: users and items block-sharded over the mesh. Ratings are
preprocessed host-side into padded per-row lists
``[n_rows_local, max_nnz]`` of (col, rating), -1 padded — the same
static-shape bucketing discipline as the exchange plane. The whole
alternating loop runs inside ONE jit (compile-once / iterate-many).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding

from sparkrdma_tpu.parallel.mesh import make_mesh, shard_spec


def _pad_rows(rows, cap):
    out_idx = np.full((len(rows), cap), -1, dtype=np.int32)
    out_val = np.zeros((len(rows), cap), dtype=np.float32)
    for i, lst in enumerate(rows):
        k = min(len(lst), cap)
        if k:
            arr = np.asarray(lst[:k])
            out_idx[i, :k] = arr[:, 0]
            out_val[i, :k] = arr[:, 1]
    return out_idx, out_val


class ALS:
    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        rank: int = 8,
        reg: float = 0.1,
    ):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.num_shards = math.prod(self.mesh.shape.values())
        self.rank = rank
        self.reg = reg
        self._cache = {}

    # ------------------------------------------------------------------
    def prepare(self, ratings: np.ndarray, n_users: int, n_items: int):
        """ratings: [m, 3] (user, item, rating). Returns padded per-user
        and per-item lists sharded over the mesh."""
        e = self.num_shards
        nu = int(math.ceil(n_users / e))
        ni = int(math.ceil(n_items / e))
        by_user = [[] for _ in range(e * nu)]
        by_item = [[] for _ in range(e * ni)]
        for u, i, r in ratings:
            u, i = int(u), int(i)
            by_user[u].append((i, float(r)))
            by_item[i].append((u, float(r)))
        cap_u = max(1, max(len(x) for x in by_user))
        cap_i = max(1, max(len(x) for x in by_item))
        u_idx, u_val = _pad_rows(by_user, cap_u)
        i_idx, i_val = _pad_rows(by_item, cap_i)
        return (u_idx, u_val, i_idx, i_val, nu, ni)

    # ------------------------------------------------------------------
    def _build(self, nu, ni, cap_u, cap_i, iters):
        axes = tuple(self.mesh.axis_names)
        spec2 = shard_spec(self.mesh)
        k = self.rank
        reg = self.reg

        def solve_side(own_idx, own_val, other_all):
            # own_idx/val: [n_local, cap]; other_all: [N_other, k]
            def per_row(idx, val):
                valid = (idx >= 0).astype(jnp.float32)  # [cap]
                f = other_all[jnp.maximum(idx, 0)]      # [cap, k]
                f = f * valid[:, None]
                a = f.T @ f + reg * jnp.maximum(valid.sum(), 1.0) * jnp.eye(k)
                b = f.T @ (val * valid)
                return jnp.linalg.solve(a, b)

            return jax.vmap(per_row)(own_idx, own_val)

        def shard_fn(u_idx, u_val, i_idx, i_val, u0, v0):
            def one_iter(_, carry):
                u, v = carry
                # the wide shuffle: every shard needs the other side's
                # factors — one all_gather per half-iteration
                v_all = jax.lax.all_gather(v, axes, tiled=True)  # [N_items, k]
                u_new = solve_side(u_idx, u_val, v_all)
                u_all = jax.lax.all_gather(u_new, axes, tiled=True)
                v_new = solve_side(i_idx, i_val, u_all)
                return u_new, v_new

            return jax.lax.fori_loop(0, iters, one_iter, (u0, v0))

        fn = shard_map(
            shard_fn,
            mesh=self.mesh,
            in_specs=(spec2, spec2, spec2, spec2, spec2, spec2),
            out_specs=(spec2, spec2),
            check_vma=False,
        )
        return jax.jit(fn)

    # ------------------------------------------------------------------
    def fit(
        self, ratings: np.ndarray, n_users: int, n_items: int, iters: int = 10,
        seed: int = 0,
    ) -> Tuple[np.ndarray, np.ndarray]:
        u_idx, u_val, i_idx, i_val, nu, ni = self.prepare(ratings, n_users, n_items)
        e = self.num_shards
        key = (nu, ni, u_idx.shape[1], i_idx.shape[1], iters)
        fn = self._cache.get(key)
        if fn is None:
            fn = self._build(nu, ni, u_idx.shape[1], i_idx.shape[1], iters)
            self._cache[key] = fn
        rng = np.random.default_rng(seed)
        u0 = (rng.normal(size=(e * nu, self.rank)) * 0.1).astype(np.float32)
        v0 = (rng.normal(size=(e * ni, self.rank)) * 0.1).astype(np.float32)
        sharding = NamedSharding(self.mesh, shard_spec(self.mesh))
        args = [
            jax.device_put(x, sharding)
            for x in (u_idx, u_val, i_idx, i_val, u0, v0)
        ]
        u, v = fn(*args)
        return np.asarray(u)[:n_users], np.asarray(v)[:n_items]


def rmse(u: np.ndarray, v: np.ndarray, ratings: np.ndarray) -> float:
    pred = (u[ratings[:, 0].astype(int)] * v[ratings[:, 1].astype(int)]).sum(axis=1)
    return float(np.sqrt(np.mean((pred - ratings[:, 2]) ** 2)))


def reference_als(
    ratings: np.ndarray, n_users: int, n_items: int, rank=8, reg=0.1,
    iters=10, seed=0, u0: Optional[np.ndarray] = None,
    v0: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Dense numpy ALS (same math, fp64) for correctness checks."""
    rng = np.random.default_rng(seed)
    u = u0.copy() if u0 is not None else rng.normal(size=(n_users, rank)) * 0.1
    v = v0.copy() if v0 is not None else rng.normal(size=(n_items, rank)) * 0.1
    by_user = [[] for _ in range(n_users)]
    by_item = [[] for _ in range(n_items)]
    for a, b, r in ratings:
        by_user[int(a)].append((int(b), r))
        by_item[int(b)].append((int(a), r))

    def solve(rows, other):
        out = np.zeros((len(rows), rank))
        for i, lst in enumerate(rows):
            if not lst:
                continue
            idx = np.array([x[0] for x in lst])
            val = np.array([x[1] for x in lst])
            f = other[idx]
            a = f.T @ f + reg * len(lst) * np.eye(rank)
            out[i] = np.linalg.solve(a, f.T @ val)
        return out

    for _ in range(iters):
        u = solve(by_user, v)
        v = solve(by_item, u)
    return u.astype(np.float32), v.astype(np.float32)
