"""Model step, serving: the share of the decode and prefill programs'
device time in the traced window spent in the expert layers (scopes
``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``, and the
grouped-product kernel, which XLA names ``ragged-dot`` and leaves without a
scope path)."""

from benchmark.metrics import _mimo


def read(obs):
    return _mimo.share_of_programs(obs, *_mimo.MOE_SCOPES, kernel=_mimo.GROUPED_KERNEL)
