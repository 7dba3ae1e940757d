"""On-chip test subset (VERDICT r4 missing #3): the device-path tests
that must hold on REAL TPU hardware, not only on the CPU farm.

Run on the chip (through the chip tool):
``SRT_TPU_TESTS=1 python -m pytest tests/test_on_chip.py -m tpu -q``
(conftest.py skips the CPU pin in that mode, so JAX takes the TPU).
Under the normal CI run every test here skips — the platform is pinned
to CPU, which the whole rest of the suite already covers.

The subset mirrors what bit round 3: flash forward AND backward
numerics (Mosaic-compiled kernels behave differently from the CPU
interpreter), the TeraSort step (device_sort + exchange), and the
typed stage_view path (host->HBM DMA with dtype reinterpretation).
First compile on the chip takes ~20-40 s per executable; shapes here
are kept small and few.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

tpu_only = pytest.mark.skipif(
    jax.devices()[0].platform == "cpu",
    reason="on-chip subset; run with SRT_TPU_TESTS=1 -m tpu",
)

pytestmark = [pytest.mark.tpu, tpu_only]


def test_flash_attention_forward_on_chip():
    from sparkrdma_tpu.ops.pallas_attention import flash_attention
    from sparkrdma_tpu.ops.ring_attention import reference_attention

    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, 256, 2, 64)).astype(np.float32))
        for _ in range(3)
    )
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-2, rtol=2e-2
    )


def test_flash_attention_backward_on_chip():
    from sparkrdma_tpu.ops.pallas_attention import flash_attention
    from sparkrdma_tpu.ops.ring_attention import reference_attention

    rng = np.random.default_rng(1)
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, 256, 2, 64)).astype(np.float32))
        for _ in range(3)
    )

    def loss_flash(q, k, v):
        return flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128
        ).sum()

    def loss_ref(q, k, v):
        return reference_attention(q, k, v, causal=True).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-2, rtol=5e-2
        )


def test_terasort_step_on_chip():
    from sparkrdma_tpu.models import TeraSorter
    from sparkrdma_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(2)
    keys = rng.integers(0, 1 << 32, 1 << 14, dtype=np.uint32)
    sorter = TeraSorter(make_mesh(jax.devices()[:1]))
    out = sorter.sort(keys)
    np.testing.assert_array_equal(out, np.sort(keys))


def test_stage_view_typed_on_chip():
    from sparkrdma_tpu.ops.hbm_arena import DeviceBufferManager

    mgr = DeviceBufferManager()
    try:
        rng = np.random.default_rng(3)
        payload = rng.integers(0, 256, 64 * 1024, np.uint8).tobytes()
        buf = mgr.stage_view(memoryview(payload), len(payload), np.uint32)
        assert buf.array.dtype == jnp.uint32
        assert bytes(buf.read(0, len(payload))) == payload
        # sub-class valid length: tail masked by `length`, bytes exact
        short = payload[: 40_000]
        buf2 = mgr.stage_view(memoryview(short), len(short), np.uint32)
        assert bytes(buf2.read(0, len(short))) == short
        buf.free()
        buf2.free()
        assert mgr.in_use_bytes == 0
    finally:
        mgr.stop()


def test_exchange_single_device_on_chip():
    from sparkrdma_tpu.ops.exchange import ExchangeProgram, pack_blocks, unpack_blocks
    from sparkrdma_tpu.parallel.mesh import make_mesh

    prog = ExchangeProgram(make_mesh(jax.devices()[:1]))
    send, counts = pack_blocks([b"on-chip-block"], 64)
    recv, rcounts = prog.exchange(send, counts)
    assert unpack_blocks(np.asarray(recv), np.asarray(rcounts)) == [
        b"on-chip-block"
    ]
