"""Model step, serving: the share of the decode program's device time
spent under the ``kv_gather`` scope (each slot's whole view gathered out of
the arena, reshaped and converted to float32): device time of those
operations inside the ``jit_step`` executions of the traced window over
the executions' device time.

A fusion counts wholly for its root's scope (``_scopes``), so XLA's fusion
choices draw this scope's edge: on the v5e the view's float32 conversion
fuses into ``attn_scores``, and part of it is hoisted out of the scan and
named after no scope. Read it beside ``decode_kv_view_share.serve`` (this
scope and ``attn_scores``: the whole-view read, whichever side the fusions
fall) and ``decode_unscoped_share.serve``: time that leaves this share and
turns up in one of those has not left the step."""

from benchmark.metrics import _scopes


def read(obs):
    return _scopes.share_of_program(obs, _scopes.under("kv_gather"))
