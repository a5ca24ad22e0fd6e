"""Kernels: the grouped products of the expert layers (gate, up and down
of every assignment on a held expert: the kernel XLA expands
``jax.lax.ragged_dot`` into, named ``ragged-dot`` in the trace, with what
else runs under the scope ``moe_experts``) against their roofline, inside the decode program's executions of the
traced window. Work: the mean ``expert_tokens`` and ``experts_touched`` of
the decode chunks delivered in the window, times the executions (a
delivery lags its dispatch by the pipeline's depth, so the chunks counted
are the executions' neighbours, not the same ones). max(FLOPs / peak,
bytes / bandwidth) over the device time under the scope."""

from benchmark import flops, moe_cost, trace_reduce as tr
from benchmark.metrics import _mimo, _scopes
from benchmark.peaks import peaks_for


def read(obs):
    chunks = _mimo.deliveries(obs, kinds=("chunk",))
    if not chunks or "trace" not in obs:
        return None
    runs = tr.module_events(obs["trace"], obs["trace_window"], obs["program_name"])
    if not runs:
        return None
    seconds = (_scopes.seconds_where(obs, _scopes.under("moe_experts"), within=runs) or 0.0) \
        + _mimo.kernel_seconds(obs, runs)
    if not seconds:
        return None
    n = len(runs) / len(chunks)
    cost = moe_cost.grouped_matmul_cost(
        obs["sizes"], n * sum(int(c["expert_tokens"]) for c in chunks),
        n * sum(int(c["experts_touched"]) for c in chunks))
    return 100.0 * flops.roofline_seconds(cost, peaks_for(obs["device_kind"])) / seconds
