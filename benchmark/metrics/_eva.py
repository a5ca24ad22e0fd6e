"""What the EvaByte cell's readers share: the executions of its two device
programs in the traced window (the decode chunk ``jit_step`` and the
prefill chunk ``jit_prefill_chunk``), device time under a scope inside them,
and the two kinds' stats that ride on the engine's
``serving.engine.dispatch`` regions. A run of another configuration, of the
parent (no such program, no such stats) or without a trace gives every
reader nothing to read: None, never 0 and never an exception."""

from benchmark import trace_reduce as tr
from benchmark.metrics import _mimo, _scopes, _spans

#: the Pallas kernel of the decode attention, found by its name
DECODE_KERNEL = "paged_decode_attention"


def has_sizes(obs):
    return obs.get("kind") == "serve" and "chunk_size" in obs.get("sizes", {})


def program_runs(obs, names=("program_name", "prefill_program_name")):
    if not has_sizes(obs) or "trace" not in obs:
        return []
    runs = []
    for key in names:
        if obs.get(key):
            runs += tr.module_events(obs["trace"], obs["trace_window"], obs[key])
    return runs


def kernel_seconds(obs, runs):
    """Device seconds of the decode attention kernels inside ``runs``."""
    return _mimo.kernel_seconds(obs, runs, DECODE_KERNEL)


def share_of_programs(obs, *scopes):
    """Percent of the decode and prefill programs' device time in the
    traced window spent under any of ``scopes``; None where nothing was."""
    runs = program_runs(obs)
    if not runs:
        return None
    seconds = _scopes.seconds_where(obs, _scopes.under(*scopes), within=runs)
    if not seconds:
        return None
    return 100.0 * seconds / (sum(m.dur_ns for m in runs) / 1e9)


def dispatches(obs):
    """Stats of the traced window's decode dispatches that say what each
    kind holds and reads."""
    if not has_sizes(obs) or "trace_window" not in obs:
        return []
    spans = _spans.inside(_spans.serving_spans(obs), obs["trace_window"],
                          "serving.engine.dispatch")
    return [s.stats for s in spans if "local_blocks_read" in s.stats]


def mean_rows_read(obs):
    """(local positions, summary rows) the dispatches' last steps read, mean
    over the traced window's dispatches; None without any."""
    stats = dispatches(obs)
    if not stats:
        return None
    bt = obs["kv_block_t"]
    return (bt * sum(int(d["local_blocks_read"]) for d in stats) / len(stats),
            bt * sum(int(d["summary_blocks_read"]) for d in stats) / len(stats))
