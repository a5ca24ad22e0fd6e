"""The closed training loop both training runners time: one dispatch per
step, a fresh batch from the seed put on the device each step, at most two
steps in flight, the window closed by ``block_until_ready`` on the last."""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

import numpy as np

from . import correct, harness, traffic

FIRST_STEPS = 3      # driven in set-up, followed by the reference
TRACED_STEPS = 5


def first_batches(cell: harness.Cell, vocab: int) -> np.ndarray:
    """The batches of the first steps, as the feed makes them: [steps, ...]."""
    return np.stack([traffic.batch(cell.mix, vocab, cell.seed, i)
                     for i in range(FIRST_STEPS)])


def run_window(step: Callable[[Any], Any], feed: Callable[[int], Any],
               first_step: int, seconds: float,
               profiler: Optional[harness.Profiler]) -> Dict[str, Any]:
    """``step(batch)`` dispatches one training step (the caller's closure
    threads the state) and returns the loss array; ``feed(i)`` makes step
    i's batch on the device. Untraced run: steps for ``seconds``. Traced
    run: steps for 0.6 * ``seconds`` untraced (the rate comes from these),
    then ``TRACED_STEPS`` steps under the profiler."""
    untimed = seconds if profiler is None else 0.6 * seconds
    i = first_step
    pending = None
    start = time.perf_counter()
    while True:
        with harness.span("bench.feed"):
            batch = feed(i)
        with harness.span("bench.dispatch"):
            loss = step(batch)
        i += 1
        if pending is not None:
            with harness.span("bench.block_until_ready"):
                pending.block_until_ready()
        pending = loss
        if time.perf_counter() - start >= untimed:
            break
    pending.block_until_ready()
    elapsed = time.perf_counter() - start
    out = {"steps": i - first_step, "window_s": elapsed, "last_loss": float(pending)}
    if profiler is not None:
        profiler.start()
        pending = None
        for _ in range(TRACED_STEPS):
            with harness.span("bench.feed"):
                batch = feed(i)
            with harness.span("bench.dispatch"):
                loss = step(batch)
            i += 1
            if pending is not None:
                with harness.span("bench.block_until_ready"):
                    pending.block_until_ready()
            pending = loss
        with harness.span("bench.block_until_ready"):
            pending.block_until_ready()
        profiler.stop()
        out["traced_steps"] = TRACED_STEPS
    out["next_step"] = i
    return out


def outcome(cell: harness.Cell, devices, *, sizes: Dict[str, int], vocab_run: int,
            program_name: str, window: Dict[str, Any], readings: Dict[str, Any],
            reference: Dict[str, Any], setup_s: float, peak: int, temp_bytes: int,
            profiler: Optional[harness.Profiler], **extra: Any) -> harness.Outcome:
    """What a training runner hands back: the observations its metric
    readers need and the checks against the reference."""
    tokens = traffic.tokens_per_batch(cell.mix)
    obs = {
        "kind": "train", "chips": cell.chips, "sizes": sizes,
        "seq": int(cell.mix["shape"][-1]), "batch_shape": list(cell.mix["shape"]),
        "device_kind": devices[0].device_kind,
        "tokens_per_s": window["steps"] * tokens / window["window_s"],
        "vocab_run": vocab_run, "program_name": program_name,
    }
    return harness.Outcome(
        obs=obs, attempted=window["steps"] + FIRST_STEPS, failed=0, setup_s=setup_s,
        checks=correct.train_checks(readings, reference, cell.limits),
        memory_peak_bytes=peak, trace_dir=profiler.dir if profiler else None,
        extra={"steps": window["steps"], "last_loss": window["last_loss"],
               "program_temp_bytes": temp_bytes, **extra})
