"""What the SDAR cell's readers share: the executions of its two device
programs in the traced window (the decode dispatch ``jit_step``, ``chunk``
forward passes of every slot, and the prefill chunk ``jit_prefill_chunk``),
device time under a scope inside them, and the block counters that ride on
the engine's ``serving.engine.deliver`` regions. A run of another
configuration, of the parent (no such family: it cannot run the cell at
all) or without a trace gives every reader nothing to read: None, never 0
and never an exception."""

from benchmark import trace_reduce as tr
from benchmark.metrics import _mimo, _scopes, _spans

#: the Pallas kernel of the block attention, found by its name
KERNEL = "paged_decode_attention"
#: XLA's expansion of ``jax.lax.ragged_dot``: no scope path, found by name
GROUPED_KERNEL = _mimo.GROUPED_KERNEL
#: device seconds, inside some executions, of the operations of one name
kernel_seconds = _mimo.kernel_seconds


def has_sizes(obs):
    return obs.get("kind") == "serve" and "block_len" in obs.get("sizes", {})


def step_runs(obs):
    """The decode dispatches' executions inside the traced window."""
    if not has_sizes(obs) or "trace" not in obs or not obs.get("program_name"):
        return []
    return tr.module_events(obs["trace"], obs["trace_window"], obs["program_name"])


def seconds_of(runs):
    return sum(m.dur_ns for m in runs) / 1e9


def scope_seconds(obs, runs, *scopes):
    return _scopes.seconds_where(obs, _scopes.under(*scopes), within=runs) or 0.0


def deliveries(obs):
    """Stats of the traced window's decode deliveries that carry the block
    counters."""
    if not has_sizes(obs) or "trace_window" not in obs:
        return []
    spans = _spans.inside(_spans.serving_spans(obs), obs["trace_window"],
                          "serving.engine.deliver")
    return [s.stats for s in spans
            if s.stats.get("kind") == "chunk" and "forwards" in s.stats]


def total(stats, key):
    return sum(int(d[key]) for d in stats)


def per_pass(obs, key):
    """Mean of a delivery's counter over the passes of its dispatch (a
    delivery lags its dispatch by the pipeline's depth, so the dispatches
    counted are the traced executions' neighbours, not the same ones)."""
    stats = deliveries(obs)
    if not stats:
        return None
    return total(stats, key) / (len(stats) * obs["decode_chunk"])
