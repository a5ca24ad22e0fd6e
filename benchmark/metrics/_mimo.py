"""What the MiMo cell's readers share: the executions of its two device
programs in the traced window (the decode chunk ``jit_step`` and the
prefill chunk ``jit_prefill_chunk``), device time under a scope inside
them, and the expert layers' counters that ride on the engine's
``serving.engine.deliver`` regions."""

from benchmark import trace_reduce as tr
from benchmark.metrics import _scopes, _spans

MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
#: XLA expands ``jax.lax.ragged_dot`` into a kernel of its own whose
#: operation keeps no scope path: it is found by its name
GROUPED_KERNEL = "ragged-dot"


def has_sizes(obs):
    return obs.get("kind") == "serve" and "held_experts" in obs.get("sizes", {})


def program_runs(obs, names=("program_name", "prefill_program_name")):
    if not has_sizes(obs) or "trace" not in obs:
        return []
    runs = []
    for key in names:
        if obs.get(key):
            runs += tr.module_events(obs["trace"], obs["trace_window"], obs[key])
    return runs


def kernel_seconds(obs, runs, name=GROUPED_KERNEL):
    """Device seconds, inside ``runs``, of the operations named ``name``."""
    return sum(s for op, s in _scopes.op_seconds(obs, within=runs).items()
               if name in tr.short_name(op))


def share_of_programs(obs, *scopes, kernel=None):
    """Percent of the decode and prefill programs' device time in the
    traced window spent under any of ``scopes`` (and in the operations
    named ``kernel``); None where nothing was."""
    runs = program_runs(obs)
    if not runs:
        return None
    seconds = _scopes.seconds_where(obs, _scopes.under(*scopes), within=runs) or 0.0
    if kernel:
        seconds += kernel_seconds(obs, runs, kernel)
    if not seconds:
        return None
    return 100.0 * seconds / (sum(m.dur_ns for m in runs) / 1e9)


def deliveries(obs, kinds=("chunk", "first")):
    """Stats of the ``serving.engine.deliver`` regions of the traced window
    that carry expert counters."""
    if not has_sizes(obs) or "trace_window" not in obs:
        return []
    spans = _spans.inside(_spans.serving_spans(obs), obs["trace_window"],
                          "serving.engine.deliver")
    return [s.stats for s in spans
            if s.stats.get("kind") in kinds and "expert_tokens" in s.stats]


def dispatches(obs):
    if not has_sizes(obs) or "trace_window" not in obs:
        return []
    spans = _spans.inside(_spans.serving_spans(obs), obs["trace_window"],
                          "serving.engine.dispatch")
    return [s.stats for s in spans if "window_blocks" in s.stats]
