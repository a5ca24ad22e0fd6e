"""Kernels: the grouped products of the latent expert layers (``grouped_relu2``
and ``grouped_matmul`` of every assignment on a held expert, under the scope
``moe_experts``) against their roofline, inside the decode program's
executions of the traced window. Work: the mean ``expert_tokens`` and
``experts_touched`` of the decode chunks delivered in the window, times the
executions, counted for an expert of TWO matrices in the latent
(``nemotron_cost.grouped_cost``; ``moe_cost`` counts three at the hidden
width). max(FLOPs / peak, bytes / bandwidth) over the device time under the
scope."""

from benchmark import flops, nemotron_cost
from benchmark.metrics import _nemotron
from benchmark.peaks import peaks_for


def read(obs):
    runs, chunks = _nemotron.runs(obs), _nemotron.deliveries(obs)
    seconds = _nemotron.scope_seconds(obs, "moe_experts", runs) if runs and chunks else 0.0
    if not seconds:
        return None
    n = len(runs) / len(chunks)
    cost = nemotron_cost.grouped_cost(
        obs["sizes"], n * sum(int(c["expert_tokens"]) for c in chunks),
        n * sum(int(c["experts_touched"]) for c in chunks))
    return 100.0 * flops.roofline_seconds(cost, peaks_for(obs["device_kind"])) / seconds
