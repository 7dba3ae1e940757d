"""Distributed transformer train step — dp x sp x tp in one jit.

A compact demonstration that the framework's mesh vocabulary composes
into a real training step (the thing the multi-chip dry-run validates):

- **dp**: batch sharded over the ``dp`` axis; gradients psum across it,
- **sp**: sequence sharded over the ``sp`` axis; exact ring attention
  (kv blocks hop neighbour-to-neighbour with an online softmax — the
  same schedule as :mod:`sparkrdma_tpu.ops.ring_attention`),
- **tp**: the MLP hidden dimension Megatron-sharded over the ``tp``
  axis; activations stay replicated on tp, the second matmul's partial
  sums reduce with one psum.

Everything — forward, ring hops, tp reduction, loss, backward (via
jax.value_and_grad inside shard_map), cross-shard gradient reduction,
SGD update — runs inside ONE jitted SPMD program, compile-once.

Weights: attention projections replicated (their grads psum over
dp+sp; tp shards compute identical copies); W1 [D, H/tp] and
W2 [H/tp, D] are tp-local (their grads psum over dp+sp only).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.ops.ulysses_attention import ulysses_shard_attention

NEG_INF = -1e30


@jax.custom_vjp
def _tp_copy(x):
    """Megatron's "f" operator: identity forward, all-reduce backward.

    The column-parallel matmul consumes a tp-replicated activation;
    each tp shard's backward produces only its slice's contribution to
    dx, so the cotangent must psum over tp here — otherwise every
    parameter upstream of the MLP receives a partial gradient."""
    return x


def _tp_copy_fwd(x):
    return x, None


def _tp_copy_bwd(_, ct):
    return (jax.lax.psum(ct, "tp"),)


_tp_copy.defvjp(_tp_copy_fwd, _tp_copy_bwd)


@jax.custom_vjp
def _tp_psum(x):
    """Megatron's "g" operator: all-reduce forward, identity backward.

    Conjugate of :func:`_tp_copy`. A bare ``lax.psum`` cannot be used
    here: under ``shard_map(check_vma=False)`` psum transposes to psum,
    so the row-parallel matmul's cotangent would arrive multiplied by
    the tp group size (the replicated downstream cotangent gets summed
    over tp), scaling the w1/w2 gradients by exactly ``tp``. The
    correct adjoint of "replicated ct through an all-reduce" is the
    identity — each tp shard already holds the full cotangent."""
    return jax.lax.psum(x, "tp")


def _tp_psum_fwd(x):
    return jax.lax.psum(x, "tp"), None


def _tp_psum_bwd(_, ct):
    return (ct,)


_tp_psum.defvjp(_tp_psum_fwd, _tp_psum_bwd)


def make_training_mesh(devices=None) -> Mesh:
    """(dp, sp, tp) mesh over 8+ devices (2x2x2 at 8)."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if n % 8 == 0:
        shape = (n // 4, 2, 2)
    elif n % 4 == 0:
        shape = (n // 4, 2, 2)
    elif n % 2 == 0:
        shape = (n // 2, 2, 1)
    else:
        shape = (1, 1, 1)
        devices = devices[:1]
    k = shape[0] * shape[1] * shape[2]
    return Mesh(np.array(devices[:k]).reshape(shape), ("dp", "sp", "tp"))


def init_params(d_model: int, n_heads: int, d_hidden: int, tp: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    s = 0.02

    def w(*shape):
        return (rng.normal(size=shape) * s).astype(np.float32)

    return {
        "wq": w(d_model, d_model),
        "wk": w(d_model, d_model),
        "wv": w(d_model, d_model),
        "wo": w(d_model, d_model),
        "w1": w(d_model, d_hidden),  # sharded on dim 1 over tp
        "w2": w(d_hidden, d_model),  # sharded on dim 0 over tp
    }


class TransformerStep:
    """One-layer attention+MLP block with an SGD train step.

    ``attn`` selects the sequence-parallel schedule:

    - ``"ring"`` (default): kv blocks hop neighbour-to-neighbour over
      the sp axis with an online-softmax accumulation — O(s/sp) memory,
      jnp-level math, differentiated by autodiff through ppermute.
    - ``"ulysses"``: two ``all_to_all``s re-shard seq<->heads and the
      full-sequence attention per head group runs through the Pallas
      flash kernel — trainable thanks to the kernel's custom VJP, so
      the backward also never materializes [Sq, Sk]. Requires
      ``n_heads % sp == 0``.
    """

    def __init__(self, mesh: Optional[Mesh] = None, n_heads: int = 4,
                 lr: float = 0.1, attn: str = "ring"):
        if attn not in ("ring", "ulysses"):
            raise ValueError(f"unknown attn schedule {attn!r}")
        self.mesh = mesh if mesh is not None else make_training_mesh()
        if attn == "ulysses" and n_heads % self.mesh.shape["sp"] != 0:
            raise ValueError(
                f"ulysses needs n_heads ({n_heads}) divisible by the sp "
                f"axis ({self.mesh.shape['sp']})"
            )
        self.n_heads = n_heads
        self.lr = lr
        self.attn = attn
        self._cache: Dict = {}

    # ------------------------------------------------------------------
    def _build(self, b, s, d, h):
        mesh = self.mesh
        sp = mesh.shape["sp"]
        heads = self.n_heads
        lr = self.lr
        dhead = d // heads

        x_spec = P("dp", "sp", None)
        rep = P()
        w1_spec = P(None, "tp")
        w2_spec = P("tp", None)
        pspecs = {
            "wq": rep, "wk": rep, "wv": rep, "wo": rep,
            "w1": w1_spec, "w2": w2_spec,
        }

        def ring_attn(q, k, v):
            # q/k/v: [b_loc, s_loc, H, dh]; ring over the sp axis
            perm = [(i, (i + 1) % sp) for i in range(sp)]
            bl, sl = q.shape[0], q.shape[1]
            m = jnp.full((bl, heads, sl), NEG_INF, jnp.float32)
            num = jnp.zeros((bl, sl, heads, dhead), jnp.float32)
            den = jnp.zeros((bl, heads, sl), jnp.float32)
            scale = 1.0 / math.sqrt(dhead)
            kb, vb = k, v
            for hop in range(sp):
                sc = jnp.einsum("bqhd,bkhd->bhqk", q, kb).astype(jnp.float32) * scale
                m_new = jnp.maximum(m, sc.max(-1))
                corr = jnp.exp(m - m_new)
                p = jnp.exp(sc - m_new[..., None])
                num = num * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
                    "bhqk,bkhd->bqhd", p, vb.astype(jnp.float32)
                )
                den = den * corr + p.sum(-1)
                m = m_new
                if hop != sp - 1:
                    kb = jax.lax.ppermute(kb, "sp", perm)
                    vb = jax.lax.ppermute(vb, "sp", perm)
            return (num / den.transpose(0, 2, 1)[..., None]).astype(q.dtype)

        def ulysses_attn(q, k, v):
            # one shared shard-level schedule (ops/ulysses_attention):
            # seq-gather / head-scatter, full-seq flash per head group,
            # inverse exchange — gradients flow through all_to_all (its
            # own transpose) and the flash kernel's custom VJP
            return ulysses_shard_attention(q, k, v, "sp", sp, causal=False)

        attn_fn = ring_attn if self.attn == "ring" else ulysses_attn

        def forward_local(params, x):
            bl, sl, _ = x.shape
            def qkv(w):
                return (x @ w).reshape(bl, sl, heads, dhead)

            attn = attn_fn(qkv(params["wq"]), qkv(params["wk"]), qkv(params["wv"]))
            x = x + attn.reshape(bl, sl, d) @ params["wo"]
            # Megatron MLP: column-parallel w1, row-parallel w2; the
            # _tp_copy/psum pair is the f/g conjugate operator pair
            hcol = jax.nn.gelu(_tp_copy(x) @ params["w1"])  # [bl, sl, H/tp]
            mlp = _tp_psum(hcol @ params["w2"])
            return x + mlp

        # global element count is static: every (dp, sp) shard holds an
        # equal tile of the [b, s, d] batch
        n_shards = mesh.shape["dp"] * mesh.shape["sp"]

        def train_shard(params, x, y):
            # The differentiated function must return the LOCAL loss
            # contribution (no dp/sp psum inside): under
            # check_vma=False psum transposes to psum, so a psum'd loss
            # seeds every shard with the full group cotangent and the
            # explicit psum(grads) below would then double-count by a
            # factor of dp*sp. Sum-reduce local grads/losses AFTER the
            # backward instead.
            def loss_fn(p):
                out = forward_local(p, x)
                return ((out - y) ** 2).sum()

            gcount = jnp.asarray(x.size * n_shards, jnp.float32)
            sq, grads = jax.value_and_grad(loss_fn)(params)
            loss = jax.lax.psum(sq, ("dp", "sp")) / gcount
            # cross-shard reduction: every param's grad sums over dp+sp;
            # tp-sharded params keep their local slice, replicated params
            # computed identical grads on every tp shard (x replicated on
            # tp), so no tp reduction is needed for either kind
            grads = jax.tree.map(
                lambda g: jax.lax.psum(g, ("dp", "sp")) / gcount, grads
            )
            new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
            return loss, new

        fn = shard_map(
            train_shard,
            mesh=mesh,
            in_specs=(pspecs, x_spec, x_spec),
            out_specs=(P(), pspecs),
            check_vma=False,
        )
        return jax.jit(fn)

    # ------------------------------------------------------------------
    def place(self, params, x, y):
        mesh = self.mesh
        def put(a, spec):
            return jax.device_put(a, NamedSharding(mesh, spec))

        pl = {
            "wq": put(params["wq"], P()),
            "wk": put(params["wk"], P()),
            "wv": put(params["wv"], P()),
            "wo": put(params["wo"], P()),
            "w1": put(params["w1"], P(None, "tp")),
            "w2": put(params["w2"], P("tp", None)),
        }
        return pl, put(x, P("dp", "sp", None)), put(y, P("dp", "sp", None))

    def _get_step_fn(self, b, s, d, h):
        key = (b, s, d, h)
        fn = self._cache.get(key)
        if fn is None:
            fn = self._build(b, s, d, h)
            self._cache[key] = fn
        return fn

    def step(self, params, x, y):
        """(loss, new_params) — one SGD step, fully sharded."""
        b, s, d = x.shape
        return self._get_step_fn(b, s, d, params["w1"].shape[1])(params, x, y)

    def run_steps(self, params, x, y, n_steps: int):
        """(final_loss, new_params) after ``n_steps`` SGD steps with the
        WHOLE loop inside one executable (DESIGN.md §4: compile-once is
        the SVC pattern — even inter-step collective scheduling is
        compiled, and a K-step run costs one dispatch)."""
        b, s, d = x.shape
        h = params["w1"].shape[1]
        key = (b, s, d, h, "loop")
        loop = self._cache.get(key)
        if loop is None:
            step_fn = self._get_step_fn(b, s, d, h)

            @functools.partial(jax.jit, static_argnums=(3,))
            def loop(params, x, y, n):
                def body(_, carry):
                    _, p = carry
                    return step_fn(p, x, y)

                return jax.lax.fori_loop(
                    0, n, body, (jnp.float32(0.0), params)
                )

            self._cache[key] = loop
        return loop(params, x, y, n_steps)


def reference_step(params, x, y, n_heads: int, lr: float):
    """Single-device implementation of the identical math."""
    d = x.shape[-1]
    dhead = d // n_heads

    def forward(p, x):
        b, s, _ = x.shape
        def qkv(w):
            return (x @ w).reshape(b, s, n_heads, dhead)

        q, k, v = qkv(p["wq"]), qkv(p["wk"]), qkv(p["wv"])
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(dhead)
        att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
        x = x + att.reshape(b, s, d) @ p["wo"]
        return x + jax.nn.gelu(x @ p["w1"]) @ p["w2"]

    def loss_fn(p):
        out = forward(p, x)
        return ((out - y) ** 2).mean()

    loss, grads = jax.value_and_grad(loss_fn)(params)
    return loss, jax.tree.map(lambda p, g: p - lr * g, params, grads)
