"""What the Nemotron cell's readers share: the executions of its two device
programs in the traced window (the decode chunk ``jit_step`` and the
prefill chunk ``jit_prefill_chunk``), device time under a scope or in a
kernel of a name inside them, the expert, state and page counters that ride
on the engine's ``serving.engine.deliver`` regions, and the prefill chunks'
positions on their ``serving.engine.launch`` regions. A run of another
configuration, of the parent (no such family: it cannot run the cell at
all) or without a trace gives every reader nothing to read: None, never 0
and never an exception."""

from benchmark import trace_reduce as tr
from benchmark.metrics import _mimo, _scopes, _spans

#: the Pallas kernel of the Mamba layers' decode update, found by its name
STATE_KERNEL = "ssm_decode_update"
kernel_seconds = _mimo.kernel_seconds


def has_sizes(obs):
    return obs.get("kind") == "serve" and "ssm_state" in obs.get("sizes", {})


def runs(obs, key="program_name"):
    """The executions inside the traced window of the decode program (or of
    the program ``obs[key]`` names)."""
    if not has_sizes(obs) or "trace" not in obs or not obs.get(key):
        return []
    return tr.module_events(obs["trace"], obs["trace_window"], obs[key])


def seconds_of(executions):
    return sum(m.dur_ns for m in executions) / 1e9


def deliveries(obs):
    """Stats of the traced window's decode deliveries that carry the
    counters."""
    if not has_sizes(obs) or "trace_window" not in obs:
        return []
    spans = _spans.inside(_spans.serving_spans(obs), obs["trace_window"],
                          "serving.engine.deliver")
    return [s.stats for s in spans if s.stats.get("kind") == "chunk" and "ssm_rows" in s.stats]


def per_step(obs, key):
    """Mean of a delivery's counter over the steps of its dispatch (a
    delivery lags its dispatch by the pipeline's depth, so the dispatches
    counted are the traced executions' neighbours, not the same ones)."""
    stats = deliveries(obs)
    if not stats:
        return None
    return sum(int(d[key]) for d in stats) / (len(stats) * obs["decode_chunk"])


def prefill_launches(obs):
    """(start, tokens) of the in-arena prefill chunks launched inside the
    traced window."""
    if not has_sizes(obs) or "trace_window" not in obs:
        return []
    spans = _spans.inside(_spans.serving_spans(obs), obs["trace_window"],
                          "serving.engine.launch")
    return [(int(s.stats["start"]), int(s.stats["tokens"])) for s in spans
            if s.stats.get("program") == "chunk_prefill" and "view" in s.stats]


def scope_seconds(obs, scope, executions):
    return _scopes.seconds_where(obs, _scopes.under(scope), within=executions) or 0.0
