"""Training metrics: step time, throughput, MFU, gang-schedule latency.

The reference had *no* metrics subsystem (SURVEY.md §5: "No Prometheus, no
metrics endpoints"); observability was TensorBoard-or-nothing.  Here the
north-star metrics (BASELINE.json) — images(or tokens)/sec/chip, MFU, and
gang-schedule-to-running p50 — are first-party, emitted as structured JSON
lines any scraper can consume.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import logging
import statistics
import sys
import time
from typing import Deque, Dict, List, Optional

log = logging.getLogger(__name__)


@dataclasses.dataclass
class Timer:
    """Wall-clock step timer with warmup discard.

    The first step includes XLA compilation (20-40 s on TPU); steady-state
    stats must exclude it or MFU is garbage.
    """

    warmup_steps: int = 2
    window: int = 50
    _samples: Deque[float] = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=50), repr=False
    )
    _seen: int = 0
    _t0: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self) -> float:
        assert self._t0 is not None, "stop() without start()"
        dt = time.perf_counter() - self._t0
        self._t0 = None
        self._seen += 1
        if self._seen > self.warmup_steps:
            self._samples.append(dt)
        return dt

    @property
    def mean_s(self) -> float:
        return statistics.fmean(self._samples) if self._samples else float("nan")

    @property
    def p50_s(self) -> float:
        return statistics.median(self._samples) if self._samples else float("nan")

    @property
    def steady_samples(self) -> int:
        return len(self._samples)


# Peak dense bf16 FLOP/s of one chip, keyed by ``jax.Device.device_kind``.
# Sources: Google Cloud TPU documentation, the "TPU v4", "TPU v5e",
# "TPU v5p" and "TPU v6e" system-architecture pages (v5e: 197 TFLOP/s).
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5": 459e12,       # v5p
    "TPU v6 lite": 918e12,  # v6e
}


def peak_flops(device) -> Optional[float]:
    """Per-chip peak bf16 FLOP/s of ``device`` for MFU.

    None off-TPU: a CPU has no peak worth dividing by, so no MFU is
    reported there.  A TPU whose kind is not in the table raises — a
    guessed peak would print a plausible, wrong utilization.
    """
    if device.platform != "tpu":
        return None
    try:
        return PEAK_BF16_FLOPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for device kind "
            f"{device.device_kind!r}; add it to "
            f"runtime.metrics.PEAK_BF16_FLOPS with its source "
            f"(known: {sorted(PEAK_BF16_FLOPS)})") from None


def mfu(
    flops_per_step: float,
    step_time_s: float,
    n_chips: int,
    peak_flops_per_chip: float,
) -> float:
    """Model FLOPs Utilization: achieved model FLOPs / peak hardware FLOPs.

    ``flops_per_step`` counts the model's useful FLOPs for one optimizer step
    (fwd+bwd, global batch), NOT hardware FLOPs — rematerialisation does not
    inflate MFU.
    """
    if step_time_s <= 0 or n_chips <= 0:
        return float("nan")
    return flops_per_step / (step_time_s * n_chips * peak_flops_per_chip)


@dataclasses.dataclass
class MetricsLogger:
    """Structured metric emission: one JSON object per line.

    Heir (and inversion) of the reference's logging story: operator glog
    flags + test-side GCS log shipping (SURVEY.md §5 "metrics/logging") —
    here the training runtime itself reports.
    """

    stream: object = dataclasses.field(default=None)
    static: Dict[str, object] = dataclasses.field(default_factory=dict)
    history: List[Dict[str, object]] = dataclasses.field(default_factory=list)

    def emit(self, **fields: object) -> Dict[str, object]:
        rec = {"ts": time.time(), **self.static, **fields}
        self.history.append(rec)
        out = self.stream if self.stream is not None else sys.stderr
        print(json.dumps(rec), file=out, flush=True)
        return rec

    def step(
        self,
        step: int,
        step_time_s: float,
        examples_per_step: int,
        *,
        flops_per_step: Optional[float] = None,
        n_chips: int = 1,
        peak_flops_per_chip: Optional[float] = None,
        loss: Optional[float] = None,
        **extra: object,
    ) -> Dict[str, object]:
        fields: Dict[str, object] = {
            "event": "train_step",
            "step": step,
            "step_time_s": round(step_time_s, 6),
            "examples_per_sec": round(examples_per_step / step_time_s, 3)
            if step_time_s > 0 else None,
            "examples_per_sec_per_chip": round(
                examples_per_step / step_time_s / n_chips, 3)
            if step_time_s > 0 else None,
        }
        if loss is not None:
            fields["loss"] = float(loss)
        if flops_per_step and peak_flops_per_chip:
            fields["mfu"] = round(
                mfu(flops_per_step, step_time_s, n_chips, peak_flops_per_chip), 4
            )
        fields.update(extra)
        return self.emit(**fields)
