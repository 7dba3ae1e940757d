"""Device-resident distributed hash join — the shuffle-heavy join workload.

BASELINE.md workload #3 (TPC-DS q64/q72: shuffle-heavy hash joins). The
Spark plan repartitions both tables by join key and hash-joins each
partition; here both sides radix-partition on the key's top bits, ride
ONE all_to_all each, and the local join is a sort + ``searchsorted``
probe — dense vector ops instead of a hash table, which is the
TPU-shaped way to probe (binary search over a sorted build side
vectorizes; chasing hash buckets does not).

Join shape: build side has UNIQUE keys (the dimension-table case those
TPC-DS queries hit); every probe row matches at most one build row, so
the output is exactly probe-sized — static shapes end to end. Probe
rows with no match return ``miss_value`` (left-outer semantics; filter
client-side for inner).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.models.terasort import KEY_BITS, SENTINEL
from sparkrdma_tpu.ops.sort import pack_by_partition, radix_partition
from sparkrdma_tpu.parallel.mesh import make_mesh, shard_spec


class HashJoin:
    """Compile-once distributed join over a device mesh."""

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        capacity_factor: float = 2.0,
        miss_value: int = -1,
    ):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.num_shards = math.prod(self.mesh.shape.values())
        if self.num_shards & (self.num_shards - 1):
            raise ValueError("HashJoin requires a power-of-two shard count")
        self.capacity_factor = capacity_factor
        self.miss_value = miss_value
        self._cache = {}

    # ------------------------------------------------------------------
    def _build(self, nb_local: int, np_local: int, cap_b: int, cap_p: int):
        e = self.num_shards
        axes = tuple(self.mesh.axis_names)
        spec = shard_spec(self.mesh)
        miss = self.miss_value

        def a2a(x):
            return jax.lax.all_to_all(x, axes, split_axis=0, concat_axis=0, tiled=True)

        def shard_fn(bk, bv, pk, pv):
            # bk/bv: [nb_local] build keys/values; pk/pv: [np_local] probe
            # 1) repartition both sides by key range (two exchanges)
            def scatter(keys, vals, cap):
                dest = radix_partition(keys, e, KEY_BITS)
                kslab, counts, overflow = pack_by_partition(
                    keys, dest, e, cap, fill=int(SENTINEL)
                )
                vslab, _, _ = pack_by_partition(vals, dest, e, cap, fill=miss)
                return a2a(kslab), a2a(vslab), a2a(counts), overflow

            bk2, bv2, bcnt, ovf_b = scatter(bk, bv, cap_b)
            pk2, pv2, pcnt, ovf_p = scatter(pk, pv, cap_p)
            overflow = jax.lax.pmax(
                (ovf_b | ovf_p).astype(jnp.int32), axes
            )

            # 2) local join: sort the build side, binary-search the probes
            bmask = (
                jnp.arange(cap_b)[None, :] < bcnt[:, None]
            ).reshape(-1)
            bkeys = jnp.where(bmask, bk2.reshape(-1), SENTINEL)
            order = jnp.argsort(bkeys)
            bkeys_s = bkeys[order]
            bvals_s = bv2.reshape(-1)[order]

            pmask = (
                jnp.arange(cap_p)[None, :] < pcnt[:, None]
            ).reshape(-1)
            pkeys = pk2.reshape(-1)
            pos = jnp.searchsorted(bkeys_s, pkeys)
            pos = jnp.minimum(pos, bkeys_s.shape[0] - 1)
            hit = (bkeys_s[pos] == pkeys) & pmask
            joined = jnp.where(hit, bvals_s[pos], miss)
            # [E, cap_p] rows aligned with pk2/pv2 for the caller to
            # re-associate via the returned counts
            return (
                pk2,
                pv2,
                joined.reshape(e, cap_p),
                pcnt,
                overflow,
            )

        fn = shard_map(
            shard_fn,
            mesh=self.mesh,
            in_specs=(spec, spec, spec, spec),
            out_specs=(spec, spec, spec, spec, P()),
            check_vma=False,
        )
        return jax.jit(fn)

    # ------------------------------------------------------------------
    def join(
        self,
        build_keys: np.ndarray,
        build_vals: np.ndarray,
        probe_keys: np.ndarray,
        probe_vals: np.ndarray,
    ) -> np.ndarray:
        """Left-outer join; returns [m, 3] (probe_key, probe_val,
        build_val-or-miss) rows, one per probe row (order not preserved).
        Retries with doubled bucket capacity on skew overflow."""
        e = self.num_shards

        def shard_pad(x, fill):
            n = len(x)
            n_local = int(math.ceil(n / e))
            dtype = np.uint32 if fill == int(SENTINEL) else np.int32
            out = np.full((e * n_local,), fill, dtype=dtype)
            out[:n] = x
            return out, n_local

        bk, nb = shard_pad(build_keys.astype(np.uint32), int(SENTINEL))
        bv, _ = shard_pad(build_vals.astype(np.int32), self.miss_value)
        pk, npl = shard_pad(probe_keys.astype(np.uint32), int(SENTINEL))
        pv, _ = shard_pad(probe_vals.astype(np.int32), self.miss_value)

        sharding = NamedSharding(self.mesh, shard_spec(self.mesh))
        args = [jax.device_put(x, sharding) for x in (bk, bv, pk, pv)]

        cap_b = max(8, int(math.ceil(nb / e) * self.capacity_factor))
        cap_p = max(8, int(math.ceil(npl / e) * self.capacity_factor))
        for _ in range(8):
            key = (nb, npl, cap_b, cap_p)
            fn = self._cache.get(key)
            if fn is None:
                fn = self._build(nb, npl, cap_b, cap_p)
                self._cache[key] = fn
            pk2, pv2, joined, pcnt, overflow = fn(*args)
            if not bool(overflow):
                break
            cap_b *= 2
            cap_p *= 2
        else:
            raise RuntimeError("join bucket overflow after 8 capacity doublings")

        pk2 = np.asarray(pk2).reshape(e, e, -1)
        pv2 = np.asarray(pv2).reshape(e, e, -1)
        joined = np.asarray(joined).reshape(e, e, -1)
        pcnt = np.asarray(pcnt).reshape(e, e)
        rows = []
        for d in range(e):
            for s in range(e):
                c = pcnt[d, s]
                for j in range(c):
                    k = pk2[d, s, j]
                    if k == int(SENTINEL):
                        continue  # padding rows injected by shard_pad
                    rows.append((k, pv2[d, s, j], joined[d, s, j]))
        return np.array(rows, dtype=np.int64)
