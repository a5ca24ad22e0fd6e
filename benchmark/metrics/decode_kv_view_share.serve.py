"""Model step, serving: the share of the decode program's device time
spent reading every slot's whole view: operations under ``kv_gather`` (the
gather out of the arena, reshape, float32 conversion) or ``attn_scores``
(scores, mask, softmax and context over that view) inside the ``jit_step``
executions of the traced window, over the executions' device time. The
sum does not depend on which of the two a fusion that spans them is
counted for."""

from benchmark.metrics import _scopes


def read(obs):
    return _scopes.share_of_program(obs, _scopes.under("kv_gather", "attn_scores"))
