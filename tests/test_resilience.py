"""Unit coverage for the resilience layer (docs/RESILIENCE.md).

RetryPolicy determinism, the CircuitBreaker state machine, the checksum
utility, the RPC checksum wire extension (including legacy frames), the
fault-plan spec grammar, and the error classification in shuffle/errors.py.
"""

import zlib

import pytest

from sparkrdma_tpu.locations import (
    BlockLocation,
    PartitionLocation,
    ShuffleManagerId,
)
from sparkrdma_tpu.resilience import (
    CircuitBreaker,
    CircuitOpenError,
    RetryPolicy,
    SourceHealthRegistry,
)
from sparkrdma_tpu.rpc import PublishPartitionLocationsMsg, RpcMsg
from sparkrdma_tpu.shuffle.errors import (
    ChecksumError,
    FetchFailedError,
    MetadataFetchFailedError,
    ShuffleError,
)
from sparkrdma_tpu.testing.faults import FaultPlan, FaultRule, InjectedFault
from sparkrdma_tpu.utils import checksum
from sparkrdma_tpu.utils.config import TpuShuffleConf


# ----------------------------------------------------------------------
# RetryPolicy
# ----------------------------------------------------------------------
def test_retry_policy_from_conf_and_allows():
    conf = TpuShuffleConf(
        {
            "tpu.shuffle.resilience.maxFetchAttempts": "3",
            "tpu.shuffle.resilience.retryBackoffMs": "10",
            "tpu.shuffle.resilience.retryBackoffMaxMs": "40",
            "tpu.shuffle.resilience.fetchDeadlineMs": "5000",
        }
    )
    p = RetryPolicy.from_conf(conf)
    assert p.max_attempts == 3
    assert p.allows(1) and p.allows(2)
    assert not p.allows(3)
    assert p.deadline_s() == pytest.approx(5.0)


def test_retry_policy_backoff_deterministic_and_bounded():
    p = RetryPolicy(max_attempts=5, backoff_ms=50, backoff_max_ms=400)
    # same (attempt, keys) -> same jittered delay, run to run
    a = p.backoff_s(1, 7, "exec-1", 3)
    b = p.backoff_s(1, 7, "exec-1", 3)
    assert a == b
    # different keys de-synchronize retries
    assert p.backoff_s(1, 7, "exec-2", 3) != a
    # exponential growth capped at backoff_max_ms; jitter keeps every
    # delay within [base/2, base]
    for attempt in range(5):
        base = min(50 * 2**attempt, 400) / 1000.0
        d = p.backoff_s(attempt, "k")
        assert base / 2 <= d <= base


def test_retry_policy_no_deadline_is_infinite():
    assert RetryPolicy().deadline_s() == float("inf")


# ----------------------------------------------------------------------
# CircuitBreaker
# ----------------------------------------------------------------------
def test_circuit_breaker_state_machine():
    t = [0.0]
    cb = CircuitBreaker(failure_threshold=3, open_ms=1000, clock=lambda: t[0])
    assert cb.state == "closed" and cb.allow()
    cb.record_failure()
    cb.record_failure()
    assert cb.state == "closed"
    assert cb.record_failure() is True  # third failure opens
    assert cb.state == "open" and not cb.allow()
    # a success while open/half-open doesn't reset the clock backwards
    t[0] = 0.5
    assert not cb.allow()
    t[0] = 1.1  # past open_ms: half-open admits exactly one probe
    assert cb.allow()
    assert cb.state == "half_open"
    assert not cb.allow()  # second caller blocked while the probe flies
    cb.record_success()
    assert cb.state == "closed" and cb.allow()


def test_circuit_breaker_half_open_failure_reopens():
    t = [0.0]
    cb = CircuitBreaker(failure_threshold=1, open_ms=1000, clock=lambda: t[0])
    cb.record_failure()
    assert cb.state == "open"
    t[0] = 1.5
    assert cb.allow()  # the half-open probe
    cb.record_failure()
    assert cb.state == "open"
    assert not cb.allow()
    # and it stays open for a fresh full window
    t[0] = 2.0
    assert not cb.allow()


def test_circuit_breaker_success_resets_failure_streak():
    cb = CircuitBreaker(failure_threshold=2, open_ms=1000)
    cb.record_failure()
    cb.record_success()
    cb.record_failure()
    assert cb.state == "closed"  # streak broken by the success


def test_source_health_registry_per_peer():
    conf = TpuShuffleConf(
        {"tpu.shuffle.resilience.circuitFailureThreshold": "1"}
    )
    reg = SourceHealthRegistry(conf, role="t")
    reg.record_failure("exec-bad")
    assert not reg.allow("exec-bad")
    assert reg.allow("exec-good")  # breakers are per-peer
    assert reg.states()["exec-bad"] == "open"


# ----------------------------------------------------------------------
# checksum utility
# ----------------------------------------------------------------------
def test_checksum_roundtrip_and_mismatch():
    data = b"the quick brown fox"
    algo, crc = checksum.compute(data)
    assert algo != checksum.ALGO_NONE
    assert checksum.verify(data, crc, algo)
    assert not checksum.verify(data + b"!", crc, algo)
    assert not checksum.verify(b"", crc, algo)


def test_checksum_none_and_unknown_algos_pass():
    data = b"xyz"
    assert checksum.verify(data, 0, checksum.ALGO_NONE)
    # unverifiable (unknown algo tag) must PASS, not fail the fetch
    assert checksum.verify(data, 123, 250)


def test_checksum_crc32_matches_zlib():
    data = b"payload" * 100
    _, crc = checksum.compute(data, algo=checksum.ALGO_CRC32)
    assert crc == zlib.crc32(data) & 0xFFFFFFFF
    assert checksum.verify(memoryview(data), crc, checksum.ALGO_CRC32)


# ----------------------------------------------------------------------
# RPC checksum wire extension
# ----------------------------------------------------------------------
def _mk_loc(pid, length, mkey, ck=0, algo=0):
    return PartitionLocation(
        ShuffleManagerId("host", 1234, f"exec-{mkey}"),
        pid,
        BlockLocation(0, length, mkey, checksum=ck, checksum_algo=algo),
    )


def test_publish_msg_checksum_extension_roundtrip():
    locs = [
        _mk_loc(0, 100, 7, ck=0xDEADBEEF, algo=checksum.ALGO_CRC32),
        _mk_loc(1, 200, 8, ck=0x12345678, algo=checksum.ALGO_CRC32),
    ]
    msg = PublishPartitionLocationsMsg(5, -1, locs, trace_id=0xABC)
    segments = msg.to_segments(4096)
    out = [RpcMsg.parse_segment(seg) for seg in segments]
    got = [loc for m in out for loc in m.locations]
    assert [
        (loc.partition_id, loc.block.checksum, loc.block.checksum_algo) for loc in got
    ] == [
        (0, 0xDEADBEEF, checksum.ALGO_CRC32),
        (1, 0x12345678, checksum.ALGO_CRC32),
    ]
    # trace id still parses alongside the checksum extension
    assert all(m.shuffle_id == 5 for m in out)
    assert all(m.trace_id == 0xABC for m in out)


def test_publish_msg_without_checksums_is_legacy_compatible():
    """No checksum -> no extension bytes: a legacy/foreign parser that
    knows nothing of the extension sees the exact old frame layout, and
    our parser reads such frames with zeroed checksum fields."""
    locs = [_mk_loc(0, 64, 3), _mk_loc(1, 64, 4)]
    msg = PublishPartitionLocationsMsg(2, -1, locs)
    baseline = PublishPartitionLocationsMsg(
        2,
        -1,
        [
            PartitionLocation(
                loc.manager_id, loc.partition_id,
                BlockLocation(loc.block.address, loc.block.length, loc.block.mkey),
            )
            for loc in locs
        ],
    )
    assert msg.to_segments(4096) == baseline.to_segments(4096)
    (seg,) = msg.to_segments(4096)
    m = RpcMsg.parse_segment(seg)
    assert [loc.block.checksum_algo for loc in m.locations] == [0, 0]
    assert m.shuffle_id == 2 and m.partition_id == -1


def test_publish_msg_checksum_survives_segmentation():
    """Checksums stay attached to THEIR location across segment splits."""
    locs = [
        _mk_loc(i, 10 + i, 100 + i, ck=i * 7 + 1, algo=checksum.ALGO_CRC32)
        for i in range(40)
    ]
    msg = PublishPartitionLocationsMsg(9, -1, locs)
    # small segment budget forces multiple segments
    segments = msg.to_segments(256)
    assert len(segments) > 1
    got = []
    for seg in segments:
        got.extend(RpcMsg.parse_segment(seg).locations)
    assert len(got) == 40
    for i, loc in enumerate(sorted(got, key=lambda x: x.partition_id)):
        assert loc.block.checksum == i * 7 + 1


# ----------------------------------------------------------------------
# errors classification
# ----------------------------------------------------------------------
def test_error_classification():
    mid = ShuffleManagerId("h", 1, "e")
    f = FetchFailedError(mid, 1, 2, 3, "boom")
    assert isinstance(f, ShuffleError)
    assert f.manager_id is mid and f.partition_id == 3
    assert "boom" in str(f)

    m = MetadataFetchFailedError(4, 5, "nope")
    assert isinstance(m, ShuffleError)
    assert m.shuffle_id == 4 and m.partition_id == 5

    c = ChecksumError(6, 7, "mismatch")
    assert isinstance(c, IOError)
    assert not isinstance(c, ShuffleError)  # retryable, not terminal
    assert c.shuffle_id == 6 and c.partition_id == 7

    o = CircuitOpenError("open")
    assert isinstance(o, IOError)
    assert not isinstance(o, ShuffleError)


# ----------------------------------------------------------------------
# fault-plan grammar
# ----------------------------------------------------------------------
def test_fault_rule_parse_full_grammar():
    r = FaultRule.parse("read:fail:3:after=2,delay_ms=10,peer=exec-1")
    assert (r.op, r.kind, r.count, r.after, r.delay_ms, r.peer) == (
        "read", "fail", 3, 2, 10, "exec-1"
    )
    with pytest.raises(ValueError):
        FaultRule.parse("bogus:fail")
    with pytest.raises(ValueError):
        FaultRule.parse("read:bogus")
    with pytest.raises(ValueError):
        FaultRule.parse("read")


def test_fault_plan_counting_and_after():
    plan = FaultPlan.parse("read:fail:2:after=1")

    class _Chan:
        peer_desc = "exec-x"

    class _L:
        def __init__(self):
            self.failures = []

        def on_success(self, p):
            pass

        def on_failure(self, e):
            self.failures.append(e)

    listeners = [_L() for _ in range(4)]
    handled = []
    for lst in listeners:
        _, h = plan.on_read(_Chan(), lst, [bytearray(4)], [(0, 0, 4)])
        handled.append(h)
    # first call skipped (after=1), next two fire, budget then exhausted
    assert handled == [False, True, True, False]
    assert plan.injected_count("read", "fail") == 2
    assert plan.total_injected == 2
    assert isinstance(listeners[1].failures[0], InjectedFault)


def test_fault_plan_corrupt_flips_one_byte_deterministically():
    plan_a = FaultPlan.parse("read:corrupt:1", seed=42)
    plan_b = FaultPlan.parse("read:corrupt:1", seed=42)

    class _Chan:
        peer_desc = "p"

    class _L:
        def on_success(self, p):
            pass

        def on_failure(self, e):
            raise AssertionError(e)

    outs = []
    for plan in (plan_a, plan_b):
        buf = bytearray(b"\x00" * 64)
        wrapped, handled = plan.on_read(_Chan(), _L(), [memoryview(buf)], [])
        assert not handled
        wrapped.on_success(None)  # corruption happens at completion
        outs.append(bytes(buf))
    assert outs[0] == outs[1]  # same seed -> same flipped byte
    assert sum(b != 0 for b in outs[0]) == 1


def test_fault_plan_peer_filter():
    plan = FaultPlan.parse("read:fail:0:peer=exec-7")

    class _Chan:
        def __init__(self, d):
            self.peer_desc = d

    class _L:
        def on_success(self, p):
            pass

        def on_failure(self, e):
            pass

    _, h1 = plan.on_read(_Chan("to exec-7 data"), _L(), [], [])
    _, h2 = plan.on_read(_Chan("to exec-9 data"), _L(), [], [])
    assert h1 and not h2


def test_fault_plan_rpc_seam():
    plan = FaultPlan.parse("rpc:drop:1")
    payload, handled = plan.on_rpc("peer", b"abc")
    assert handled
    payload, handled = plan.on_rpc("peer", b"abc")
    assert not handled and payload == b"abc"
