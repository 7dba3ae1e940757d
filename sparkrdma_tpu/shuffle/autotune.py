"""Attribution-driven wave self-tuning for the schedule compiler.

The pipelined wave engine (shuffle/collective.py) has one load-bearing
sizing choice: the effective ``collective.waveBytes``, which decides
how many waves a stage cuts into. Too coarse and the stage runs as one
monolithic wave — nothing for the pipeline to overlap; too fine and
per-wave dispatch dominates. The right cut depends on the stage shape
and the rig, so this module closes the loop from the system's own
observability planes instead of asking the operator to guess:

- ``collective.*`` wave stats from the stage that just ran (wave
  count, dispatch vs in-flight wall, overlap actually achieved),
- the job's critical-path :class:`~sparkrdma_tpu.obs.attr.TimeBreakdown`
  (PR 14) — if ``dma-wave`` is a sliver of the job's wall, re-cutting
  waves cannot move the job and the tuner holds still,
- the sampling profiler's gap frames (PR 15) — transfer-plane frames
  (``device_put`` / ``block_until_ready``) dominating untraced gaps
  confirm the mover is worth re-cutting toward overlap.

Choices persist per (shuffle, stage-shape) signature in the compiler's
tuner instance, so the SECOND identical stage of a job already runs
with the adjusted cut — the first knob the system tunes from its own
attribution data. The tuned budget never drops below the stage's
largest partition group: wave formation keeps a partition's rows in
one wave, and a tuner must never change how a stage is cut into them.

Stdlib + numpy only (the compiler imports this on every platform).
"""

from __future__ import annotations

import logging
import threading
from typing import Dict, Optional, Tuple

from sparkrdma_tpu.obs import get_registry
from sparkrdma_tpu.obs.journal import emit as journal_emit
from sparkrdma_tpu.ops.exchange import round_bucket

logger = logging.getLogger(__name__)

# fraction of job wall the dma-wave category must carry before the
# tuner will re-cut a stage on breakdown evidence; below this the
# shuffle is not the job's problem and re-cutting is churn
MIN_DMA_WAVE_FRACTION = 0.05


def stage_signature(schedule: str, lanes: int, rows_class: int,
                    bucket_class: int, dtype_name: str) -> Tuple:
    """Stable identity of a stage SHAPE: two stages with the same
    signature would compile to the same wave program classes, so a
    cut learned on one transfers to the other."""
    return (schedule, lanes, rows_class, bucket_class, dtype_name)


class WaveReport:
    """One executed stage's wave stats, fed back by ``execute()``."""

    __slots__ = ("stage_bytes", "min_group_bytes", "waves", "depth",
                 "dispatch_ms", "wave_ms", "overlap_ms")

    def __init__(self, stage_bytes: int, min_group_bytes: int, waves: int,
                 depth: int, dispatch_ms: float, wave_ms: float,
                 overlap_ms: float):
        self.stage_bytes = stage_bytes
        # largest single partition group (bucketed) — the group floor
        self.min_group_bytes = min_group_bytes
        self.waves = waves
        self.depth = depth
        self.dispatch_ms = dispatch_ms
        self.wave_ms = wave_ms
        self.overlap_ms = overlap_ms


class WaveAutoTuner:
    """Per-compiler controller: observe a stage, choose the next cut.

    Deterministic and convergent by construction: the chosen budget is
    a pure function of (stage bytes, depth, group floor), so the
    second observation of the same signature computes the same choice
    and the controller goes quiet (no oscillation)."""

    def __init__(self, conf, executor_id: str):
        self._conf = conf
        self._executor_id = executor_id
        self._lock = threading.Lock()
        self._choices: Dict[Tuple, int] = {}
        reg = get_registry()
        self._m_adjust = reg.counter(
            "collective.autotune_adjustments", role=executor_id
        )
        self._m_tuned = reg.gauge(
            "collective.tuned_wave_bytes", role=executor_id
        )

    # ------------------------------------------------------------------
    def wave_bytes_for(self, sig: Tuple) -> Optional[int]:
        """The remembered cut for this stage shape, or None for the
        configured default. Called by ``plan()`` before wave
        formation — this is how the second identical stage runs
        tuned."""
        if not self._conf.collective_auto_tune:
            return None
        with self._lock:
            return self._choices.get(sig)

    # ------------------------------------------------------------------
    def observe(self, sig: Tuple, report: WaveReport) -> None:
        """Fold one executed stage into the per-signature choice."""
        if not self._conf.collective_auto_tune:
            return
        if report.stage_bytes <= 0 or report.waves <= 0:
            return
        if not self._breakdown_allows():
            return
        target = self._target_budget(report)
        if target is None:
            return
        with self._lock:
            prev = self._choices.get(sig)
            if prev == target:
                return  # converged for this shape
            self._choices[sig] = target
        self._m_adjust.inc()
        self._m_tuned.set(target)
        journal_emit(
            "autotune.adjust", role=self._executor_id,
            prev=prev or 0, wave_bytes=target, waves=report.waves,
        )
        logger.debug(
            "autotune: stage %r waveBytes %s -> %d (waves=%d depth=%d "
            "dispatch=%.2fms wall=%.2fms overlap=%.2fms)",
            sig, prev, target, report.waves, report.depth,
            report.dispatch_ms, report.wave_ms, report.overlap_ms,
        )

    # ------------------------------------------------------------------
    def _target_budget(self, report: WaveReport) -> Optional[int]:
        """The cut the NEXT run of this shape should use.

        Aim for ~2 waves per pipeline slot: enough waves that issue
        and consume genuinely overlap, few enough that dispatch stays
        amortized. When the stage already runs dispatch-bound (issue
        wall dominating the in-flight wall across many waves), coarsen
        instead — the same rule, approached from the other side."""
        depth = max(1, report.depth)
        target_waves = 2 * depth
        configured = self._conf.collective_wave_bytes
        dispatch_frac = (
            report.dispatch_ms / report.wave_ms
            if report.wave_ms > 1e-6 else 0.0
        )
        if report.waves > target_waves * 2 and dispatch_frac > 0.5:
            # dispatch-bound: coarsen toward the target count
            ideal = -(-report.stage_bytes // target_waves)
        elif report.waves < target_waves:
            # monolithic (or near): re-cut so the pipeline has waves
            # to keep in flight
            ideal = -(-report.stage_bytes // target_waves)
        else:
            return None  # already in band — hold
        budget = round_bucket(max(1, ideal))
        # never cut below the group floor (a partition's rows share
        # one wave) nor above the operator's configured cap
        budget = max(budget, report.min_group_bytes)
        budget = min(budget, configured)
        # and never below the smallest legal knob value
        budget = max(budget, 1 << 16)
        return budget

    # ------------------------------------------------------------------
    def _breakdown_allows(self) -> bool:
        """Attribution gate: when the last job's TimeBreakdown says the
        wall went elsewhere (and its gap frames don't implicate the
        transfer plane), hold still. No breakdown (critpath off, first
        job) means no veto — wave stats alone are enough to act."""
        try:
            from sparkrdma_tpu.obs.attr import dma_wave_signal, last_breakdown

            bd = last_breakdown()
            if bd is None:
                return True
            fraction, transfer_gaps = dma_wave_signal(bd)
            return fraction >= MIN_DMA_WAVE_FRACTION or transfer_gaps
        except Exception:
            logger.exception("autotune breakdown gate failed; allowing")
            return True
