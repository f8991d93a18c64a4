"""Fault tolerance & straggler mitigation for the training driver.

On a real fleet the runtime signals failures as exceptions from the jitted
step (device halt, DCN timeout) or through the coordination service. The
driver policy implemented here (launch/train.py):

  1. every step runs under the FaultSupervisor; an exception triggers
     restore-from-latest-checkpoint and (optionally) an elastic re-mesh to
     the surviving device set;
  2. the StragglerMonitor tracks a robust step-time estimate (median + MAD);
     a step slower than ``threshold`` MADs is counted against the culprit —
     on TPU fleets, persistent stragglers get the host marked for hot-spare
     swap at the next checkpoint boundary (here: reported via callback);
  3. checkpoint cadence adapts: after a failure the next checkpoint is
     immediate, then cadence decays back to the configured interval.

Tests inject synthetic failures/stragglers (tests/test_fault.py).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import numpy as np


class DeviceLossError(RuntimeError):
    """A device (or its runtime) is gone.

    Unlike a transient collective hiccup this is **not retryable in place**:
    re-running the slab on the same mesh cannot succeed. The serving layer
    catches it and runs the re-plan path (shrink mesh → remesh cached state
    → rewarm executable → replay the in-flight slab); the training driver
    maps it onto restore + elastic re-mesh.
    """

    def __init__(self, device_ids, message: str = ""):
        self.device_ids = tuple(int(i) for i in device_ids)
        super().__init__(
            message or f"lost device(s) {list(self.device_ids)}")


# Messages of deterministic compile/lowering failures: re-running the slab
# recompiles the same program and fails the same way.
_COMPILE_MARKERS = ("Mosaic failed to compile", "compile permanent error",
                    "Unimplemented primitive", "LoweringException",
                    "failed to legalize", "RESOURCE_EXHAUSTED")


def retryable(exc: BaseException) -> bool:
    """True for a transient runtime failure worth re-running in place.

    Tracing and lowering errors (``TypeError``, ``ValueError``,
    ``NotImplementedError``, Pallas lowering exceptions) and compile
    failures (a kernel Mosaic refuses, a program that does not fit HBM)
    are deterministic: they propagate at once instead of being retried.
    """
    if isinstance(exc, NotImplementedError) \
            or not isinstance(exc, RuntimeError):
        return False
    msg = str(exc)
    return not any(m in msg for m in _COMPILE_MARKERS)


@dataclasses.dataclass
class StragglerMonitor:
    threshold_mads: float = 6.0
    window: int = 64
    min_samples: int = 8
    _times: list = dataclasses.field(default_factory=list)
    stragglers: int = 0

    def observe(self, step_time: float) -> bool:
        """Record a step time; returns True if it was a straggler step."""
        times = self._times
        is_straggler = False
        if len(times) >= self.min_samples:
            med = float(np.median(times))
            mad = float(np.median(np.abs(np.asarray(times) - med))) + 1e-9
            if step_time > med + self.threshold_mads * mad and \
                    step_time > 1.5 * med:
                is_straggler = True
                self.stragglers += 1
        times.append(step_time)
        if len(times) > self.window:
            times.pop(0)
        return is_straggler

    @property
    def median(self) -> float:
        return float(np.median(self._times)) if self._times else 0.0


@dataclasses.dataclass
class FaultSupervisor:
    """Wraps the train step with restore-and-retry semantics."""

    restore_fn: Callable[[], tuple]        # () -> (step, state)
    max_restarts: int = 5
    on_failure: Optional[Callable] = None  # (exc, restart_count) -> None
    restarts: int = 0

    def run(self, step_fn: Callable, state, step: int):
        """Run one step; on failure restore from checkpoint and signal the
        caller to rebuild (returns (state, step, failed=True))."""
        try:
            return step_fn(state), step + 1, False
        except Exception as exc:  # noqa: BLE001 — any device/runtime error
            self.restarts += 1
            if self.on_failure is not None:
                self.on_failure(exc, self.restarts)
            if self.restarts > self.max_restarts:
                raise
            step, state = self.restore_fn()
            return state, step, True


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Retry/timeout/backoff policy for one slab execution.

    ``timeout_s`` is *post-hoc*: a blocking XLA dispatch cannot be aborted
    portably, so an attempt that completes but overruns the deadline is
    counted as a timeout (and feeds the straggler monitor) rather than
    cancelled mid-flight.
    """

    max_retries: int = 2
    backoff_s: float = 0.05
    backoff_factor: float = 2.0
    timeout_s: float = 120.0

    def backoff(self, attempt: int) -> float:
        return self.backoff_s * self.backoff_factor ** attempt


@dataclasses.dataclass
class ServingFaultSupervisor:
    """Request-level fault policy for the GP serving layer (DESIGN.md §15).

    Transient slab errors are retried in place with exponential backoff;
    :class:`DeviceLossError` is never retried in place — it propagates to
    the server's detect → remesh → rewarm → replay path. Every attempt's
    wall time feeds the :class:`StragglerMonitor`, so serving step times
    drive the same straggler detection as training steps.
    """

    retry: RetryPolicy = dataclasses.field(default_factory=RetryPolicy)
    monitor: StragglerMonitor = dataclasses.field(
        default_factory=StragglerMonitor)
    device_losses: int = 0
    transient_retries: int = 0
    timeouts: int = 0

    def execute(self, attempt_fn: Callable[[], "object"]):
        """Run one slab attempt to completion, retrying transient errors."""
        attempt = 0
        while True:
            t0 = time.perf_counter()
            try:
                out = attempt_fn()
            except DeviceLossError:
                self.device_losses += 1
                raise
            except Exception as exc:  # noqa: BLE001 — runtime/collective
                if attempt >= self.retry.max_retries or not retryable(exc):
                    raise
                self.transient_retries += 1
                time.sleep(self.retry.backoff(attempt))
                attempt += 1
                continue
            dt = time.perf_counter() - t0
            if dt > self.retry.timeout_s:
                self.timeouts += 1
            self.monitor.observe(dt)
            return out

    def metrics(self) -> dict:
        return {
            "device_losses": self.device_losses,
            "transient_retries": self.transient_retries,
            "timeouts": self.timeouts,
            "stragglers": self.monitor.stragglers,
            "median_step_s": self.monitor.median,
        }
