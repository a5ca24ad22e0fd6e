"""Kernels: the expanded latent prefill attention (``latent_prefill_attention``,
once a layer and chunk) against its FLOP roofline, inside the prefill
chunks' executions of the traced window. Work: the mean over the chunks
launched in the window (their ``start`` and ``tokens``, on the engine's
``serving.engine.launch`` region) of the keys each query sees and the
latent rows expanded, in the form the kernel runs
(``sarvam_cost.mla_prefill_cost``), times the executions and the layers.
max(FLOPs / peak, bytes / bandwidth) over the kernels' device time."""

from benchmark import flops, sarvam_cost
from benchmark.metrics import _sarvam
from benchmark.peaks import peaks_for


def read(obs):
    runs = _sarvam.runs(obs, "prefill_program_name")
    launches = _sarvam.prefill_launches(obs)
    seconds = _sarvam.kernel_seconds(obs, runs, _sarvam.PREFILL_KERNEL) if runs and launches else 0.0
    if not seconds:
        return None
    s = obs["sizes"]
    costs = [sarvam_cost.mla_prefill_cost(s, start, n) for start, n in launches]
    scale = len(runs) * len(s["moe_layers"]) / len(costs)
    cost = {k: scale * sum(c[k] for c in costs) for k in ("flops", "bytes")}
    return 100.0 * flops.roofline_seconds(cost, peaks_for(obs["device_kind"])) / seconds
