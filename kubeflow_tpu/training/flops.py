"""FLOP accounting and MFU, from the compiler rather than hand math.

XLA's cost analysis on the *compiled* executable counts the FLOPs actually
scheduled (fused, rematerialized, whatever) — the honest numerator for
MFU = flops_per_step / (step_seconds * peak_flops). Peak comes from the
accelerator catalog (kubeflow_tpu.tpu.topology) so control plane and
benchmark agree on the denominator.
"""

from __future__ import annotations

import time
from typing import Any, Optional, Tuple

import jax

from kubeflow_tpu.tpu.topology import ACCELERATORS


def _flops_of(compiled: Any) -> Optional[float]:
    analysis = compiled.cost_analysis()
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    if not analysis:
        return None
    flops = analysis.get("flops")
    return float(flops) if flops and flops > 0 else None


def compiled_flops(jitted_fn: Any, *args: Any, **kwargs: Any) -> Optional[float]:
    """Total FLOPs of one invocation, from XLA cost analysis (None if the
    backend doesn't report)."""
    return _flops_of(jitted_fn.lower(*args, **kwargs).compile())


def compiled_with_cost(
    jitted_fn: Any, *args: Any, **kwargs: Any
) -> Tuple[Any, Optional[float], float]:
    """Lower + compile once, returning ``(compiled, flops, compile_seconds)``.

    One AOT compile serves both the callable the bench loop runs and the
    cost analysis — the old ``compiled_flops`` + warmup-call pattern paid
    the (minutes-scale on big configs) XLA compile twice and folded it into
    the first timed window. The compile wall time comes back separately so
    telemetry (StepClock.compile / bench ``step_breakdown``) reports it
    instead of charging it to steps.
    """
    start = time.perf_counter()
    compiled = jitted_fn.lower(*args, **kwargs).compile()
    compile_s = time.perf_counter() - start
    return compiled, _flops_of(compiled), compile_s


def memory_stats(compiled: Any) -> Optional[dict]:
    """HBM footprint of a compiled executable, from the compiler's
    ``memory_analysis`` (the honest counterpart to cost-analysis FLOPs):
    argument/output/temp bytes plus their sum as ``peak_hbm_bytes`` — the
    live-bytes bound the executable needs resident, the number the
    ``training_step_peak_hbm_bytes`` gauge and bench rows report. Returns
    None when the backend doesn't implement the analysis."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes")
    try:
        out = {f.replace("_size_in_bytes", "_bytes"): int(getattr(ma, f))
               for f in fields}
    except (AttributeError, TypeError):
        return None
    out["peak_hbm_bytes"] = sum(out.values())
    return out


def peak_flops_per_chip(generation: str = "v5e") -> float:
    return ACCELERATORS[generation].bf16_tflops_per_chip * 1e12


def peak_hbm_bandwidth(generation: str = "v5e") -> float:
    """Peak HBM bytes/second per chip — the roofline's memory ceiling."""
    return ACCELERATORS[generation].hbm_gbps_per_chip * 1e9


def mfu(
    flops_per_step: float,
    step_seconds: float,
    num_chips: int = 1,
    generation: str = "v5e",
) -> float:
    """Model FLOPs utilization in [0, 1]."""
    return flops_per_step / (step_seconds * num_chips * peak_flops_per_chip(generation))


def detect_generation() -> str:
    """Map the live JAX device to a catalog generation. A device the
    catalog does not know raises: a default here would price a bench row
    against the wrong peak. CPU callers pass ``generation=`` themselves."""
    kind = jax.devices()[0].device_kind.lower()
    squashed = kind.replace(" ", "").replace("lite", "e")
    for gen in ACCELERATORS:
        if gen in squashed:
            return gen
    if "v5" in kind:  # v5p reports plain "TPU v5"
        return "v5p"
    raise ValueError(
        f"device kind {kind!r} is not in the accelerator catalog "
        f"({sorted(ACCELERATORS)}); pass generation= explicitly")
