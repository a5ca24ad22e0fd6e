"""Kernels: the Mamba layers' chunked scan (the device time under the scope
``ssm_scan`` inside the prefill chunks' executions of the traced window,
whatever implements it) against its roofline. Work: the mean over the
chunks launched in the window (their ``tokens``, on the engine's
``serving.engine.launch`` region) of ``nemotron_cost.ssm_scan_cost``, times
the executions and the Mamba layers. max(FLOPs / peak, bytes / bandwidth)
over that device time."""

from benchmark import flops, nemotron_cost
from benchmark.metrics import _nemotron
from benchmark.peaks import peaks_for


def read(obs):
    runs = _nemotron.runs(obs, "prefill_program_name")
    launches = _nemotron.prefill_launches(obs)
    seconds = _nemotron.scope_seconds(obs, "ssm_scan", runs) if runs and launches else 0.0
    if not seconds:
        return None
    s = obs["sizes"]
    costs = [nemotron_cost.ssm_scan_cost(s, n) for _, n in launches]
    scale = len(runs) * nemotron_cost.kinds(s, "M") / len(costs)
    cost = {k: scale * sum(c[k] for c in costs) for k in ("flops", "bytes")}
    return 100.0 * flops.roofline_seconds(cost, peaks_for(obs["device_kind"])) / seconds
