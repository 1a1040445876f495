"""Measured step timing: sampled `block_until_ready` brackets and the
`exchange.cost_drift` gauge.

Code under jit runs its Python once per compile, so nothing inside the step
can time it (`utils/trace.py`, "Two clocks"). This module measures the step
from outside without touching the hot path's one-device_get rule: the jitted
step stays untouched; every Nth CALL is bracketed host-side with
`jax.block_until_ready` (the "caller's timing wrapper" the oelint host-sync
pass points at) and lands in the `trainer.step_ms` histogram. All other
calls pay one integer increment. Time per STAGE of the step (and the exposed
part of the exchange) is measured on the device's clock, not modelled here:
profile a few windows (`chip_smoke.py --profile DIR`, `jax.profiler.trace`)
and read `tools/trace_report.py --xplane DIR`.

Cost drift: with the analytic wire model attached
(`MeshTrainer.last_wire_cost` → `bytes_per_step`), each sample derives
measured µs per modeled exchange byte; the first `BASELINE_SAMPLES` samples
set the baseline and `exchange.cost_drift` gauges the relative drift
(0 = the wire is priced as it was when training started; a mispriced wire
or placement policy shows up as sustained drift instead of silently
mis-steering byte-budget decisions).

Overlap awareness (round 18): software-pipelined windows move the prefetched
exchange off the critical path — the wire model marks those bytes
`overlapped_bytes`. Charging them to the sampled wall time would understate
µs/byte while pipelined and read as phantom drift the moment pipelining
toggles; so only the EXPOSED bytes (`bytes_per_step − overlapped_bytes`)
price the baseline.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

from . import metrics

BASELINE_SAMPLES = 3


class StepWatch:
    """Wrap a jitted step callable with sampled measurement.

    `every`: sample one call in N (N >= 1; the non-sampled N-1 pay a counter
    increment only). `wire_cost`: zero-arg callable returning the trainer's
    analytic exchange cost dict (or None) — read lazily at sample time
    because `MeshTrainer.last_wire_cost` is set at trace time, after wrap.
    The wrapped callable proxies attribute access (`.lower`, ...) to the
    inner jit fn so recompile guards and fingerprint pins keep working.
    """

    def __init__(self, every: int = 16, *,
                 wire_cost: Optional[Callable[[], Optional[dict]]] = None,
                 label: str = "trainer"):
        if every < 1:
            raise ValueError(f"StepWatch(every={every}): need >= 1")
        self.every = int(every)
        self.wire_cost = wire_cost
        self.label = label
        self.calls = 0
        self.samples = 0
        self.input_waits = 0
        self._baseline_us_per_byte: Optional[float] = None
        self._baseline_n = 0

    # -- per-sample folding ---------------------------------------------------

    def _observe_sample(self, ms: float) -> None:
        self.samples += 1
        metrics.observe("trainer.step_ms", ms, "hist")
        cost = self.wire_cost() if self.wire_cost is not None else None
        bytes_per_step = int((cost or {}).get("bytes_per_step", 0) or 0)
        overlapped = int((cost or {}).get("overlapped_bytes", 0) or 0)
        # pipelined windows hide `overlapped` bytes under the dense compute —
        # only the EXPOSED bytes sit on the sampled critical path, so they
        # alone price µs/byte and the drift baseline (no phantom drift when
        # pipelining toggles)
        exposed = max(bytes_per_step - overlapped, 0)
        if exposed > 0:
            us_per_byte = ms * 1e3 / exposed
            metrics.observe("exchange.us_per_byte", us_per_byte, "gauge")
            if self._baseline_n < BASELINE_SAMPLES:
                n = self._baseline_n
                base = self._baseline_us_per_byte or 0.0
                self._baseline_us_per_byte = (base * n + us_per_byte) / (n + 1)
                self._baseline_n = n + 1
            if self._baseline_us_per_byte and self._baseline_us_per_byte > 0:
                metrics.observe(
                    "exchange.cost_drift",
                    us_per_byte / self._baseline_us_per_byte - 1.0, "gauge")

    def observe_input_wait(self, ms: float) -> None:
        """The input-wait attribution lane (round 20): time the TRAIN LOOP
        spent blocked pulling the next batch off the feed ring — the
        host-side twin of the sampled `trainer.step_ms` bracket. Near-zero
        while the producer keeps the ring full (compute-bound, the healthy
        state); a share of step time that grows means input-bound, and
        `data.ingest.input_wait_share` folds the two lanes into the gauge
        tools/ingest_slo.json gates. Every wait records (waits are host
        wall time already — no device sync to amortize, unlike step_ms)."""
        self.input_waits += 1
        metrics.observe(f"{self.label}.input_wait_ms", ms, "hist")

    def wrap(self, fn):
        """-> callable with the same signature as `fn`; every Nth call is
        measured to completion (`jax.block_until_ready` on the result — the
        documented OUTSIDE-the-hot-path timing sync), the rest dispatch
        untouched."""
        return _MeasuredStep(self, fn)


class _MeasuredStep:
    """The wrapped step: calls sample through the owning StepWatch;
    everything else (`.lower`, `._cache_size`, ...) proxies to the jit fn."""

    def __init__(self, watch: StepWatch, fn):
        self._watch = watch
        self._fn = fn

    def __call__(self, *args, **kwargs):
        import jax
        w = self._watch
        w.calls += 1
        if w.calls % w.every:
            return self._fn(*args, **kwargs)
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        jax.block_until_ready(out)
        w._observe_sample((time.perf_counter() - t0) * 1e3)
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


def timed_batches(it, watch: Optional[StepWatch] = None, *,
                  label: str = "trainer"):
    """Wrap a batch iterator so each `next()`'s blocking time lands in the
    input-wait lane: `watch.observe_input_wait` when a StepWatch is given
    (counted alongside its step samples), else straight into the
    `{label}.input_wait_ms` histogram. This is the measurement point of
    tentpole (c) — put it IMMEDIATELY around the source the train loop
    blocks on (the FeedRing), with no work between `next()` and the step
    dispatch, or parse time masquerades as input wait."""
    it = iter(it)
    while True:
        t0 = time.perf_counter()
        try:
            item = next(it)
        except StopIteration:
            return
        ms = (time.perf_counter() - t0) * 1e3
        if watch is not None:
            watch.observe_input_wait(ms)
        else:
            metrics.observe(f"{label}.input_wait_ms", ms, "hist")
        yield item
