"""Kernels: the grouped products over all 128 held experts (gate, up and
down of every assignment: the kernel XLA expands ``jax.lax.ragged_dot``
into, named ``ragged-dot`` in the trace, with what else runs under the
scope ``moe_experts``) against their roofline, inside the decode dispatches
of the traced window. Work: the deliveries' ``expert_tokens`` and
``experts_touched`` (mean a pass times the passes executed). max(FLOPs /
peak, bytes / bandwidth) over that device time; at 16 rows an expert the
touched experts' weights bound it."""

from benchmark import flops, sdar_cost
from benchmark.metrics import _sdar
from benchmark.peaks import peaks_for


def read(obs):
    runs = _sdar.step_runs(obs)
    rows, touched = _sdar.per_pass(obs, "expert_tokens"), _sdar.per_pass(obs, "experts_touched")
    if not runs or rows is None:
        return None
    seconds = _sdar.scope_seconds(obs, runs, "moe_experts") \
        + _sdar.kernel_seconds(obs, runs, _sdar.GROUPED_KERNEL)
    if not seconds:
        return None
    passes = len(runs) * obs["decode_chunk"]
    cost = sdar_cost.grouped_matmul_cost(obs["sizes"], passes * rows, passes * touched)
    return 100.0 * flops.roofline_seconds(cost, peaks_for(obs["device_kind"])) / seconds
