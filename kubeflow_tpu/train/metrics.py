"""Training metrics: throughput, MFU, and the emission contract.

Emission doubles as the Katib-analog stdout metrics-collector source
((U) katib pkg/metricscollector StdOut format: "name=value" lines) and as a
JSONL file the operator scrapes onto JAXJob status (SURVEY.md §5 metrics)."""

from __future__ import annotations

import json
import sys
import time
from typing import Optional, TextIO


class Throughput:
    """Steady-state throughput over a sliding window (skips compile step)."""

    def __init__(self, tokens_per_step: float, num_chips: int,
                 flops_per_token: float, peak_tflops: Optional[float]):
        """``peak_tflops``: the chip's published bf16 peak, or None where
        there is none (the CPU) — then no ``mfu`` is reported."""
        self.tokens_per_step = tokens_per_step
        self.num_chips = num_chips
        self.flops_per_token = flops_per_token
        self.peak_flops = None if peak_tflops is None else peak_tflops * 1e12
        self._last: Optional[float] = None
        self._ema_dt: Optional[float] = None

    @property
    def ema_step_time_s(self) -> Optional[float]:
        """Smoothed steady step time (seconds); None before two ticks.
        The goodput ledger prices surviving progress with this."""
        return self._ema_dt

    def tick(self, steps_elapsed: int = 1) -> dict:
        """Update with the wall time since the previous tick, which covered
        ``steps_elapsed`` train steps (callers ticking every log interval must
        pass the interval length or all rates are off by that factor)."""
        now = time.perf_counter()
        out: dict = {}
        if self._last is not None and steps_elapsed > 0:
            dt = (now - self._last) / steps_elapsed
            self._ema_dt = dt if self._ema_dt is None else 0.9 * self._ema_dt + 0.1 * dt
            tps = self.tokens_per_step / self._ema_dt
            out = {
                "step_time_ms": self._ema_dt * 1e3,
                "tokens_per_sec": tps,
                "tokens_per_sec_per_chip": tps / self.num_chips,
            }
            if self.peak_flops is not None:
                out["mfu"] = (self.flops_per_token * tps) / (
                    self.num_chips * self.peak_flops)
        self._last = now
        return out


class MetricsEmitter:
    """Writes `name=value` lines to stdout (tune collector contract) and
    JSON lines to an optional file (operator scrape)."""

    def __init__(self, jsonl_path: Optional[str] = None, stream: Optional[TextIO] = None):
        self.stream = stream or sys.stdout
        self.jsonl_path = jsonl_path
        self.jsonl = open(jsonl_path, "a") if jsonl_path else None

    def emit(self, step: int, metrics: dict) -> None:
        flat = {k: (float(v) if hasattr(v, "item") or isinstance(v, (int, float)) else v)
                for k, v in metrics.items()}
        parts = " ".join(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
                         for k, v in sorted(flat.items()))
        print(f"step={step} {parts}", file=self.stream, flush=True)
        if self.jsonl:
            self.jsonl.write(json.dumps({"step": step, **flat}) + "\n")
            self.jsonl.flush()

    def close(self) -> None:
        if self.jsonl:
            self.jsonl.close()
