"""What the Sarvam cell's readers share: the executions of its two device
programs in the traced window (the decode chunk ``jit_step`` and the
prefill chunk ``jit_prefill_chunk``), the device time of its two latent
attention kernels inside them, the expert and latent-page counters that
ride on the engine's ``serving.engine.deliver`` regions, and the prefill
chunks' positions on their ``serving.engine.launch`` regions. A run of
another configuration, of the parent (no such family: it cannot run the
cell at all) or without a trace gives every reader nothing to read: None,
never 0 and never an exception."""

from benchmark import trace_reduce as tr
from benchmark.metrics import _mimo, _scopes, _spans

#: the Pallas kernels of the latent attention, found by their names
DECODE_KERNEL = "latent_decode_attention"
PREFILL_KERNEL = "latent_prefill_attention"
#: device seconds, inside some executions, of the operations of one name
kernel_seconds = _mimo.kernel_seconds


def has_sizes(obs):
    return obs.get("kind") == "serve" and "kv_lora_rank" in obs.get("sizes", {})


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
    return [s.stats for s in spans
            if s.stats.get("kind") == "chunk" and "latent_pages_read" in s.stats]


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


def busy_share(obs, scope):
    """Percent of the device's busy time in the traced window spent under
    ``scope``; None where nothing ran under it."""
    if not has_sizes(obs) or "busy_by_device" not in obs:
        return None
    seconds = _scopes.seconds_where(obs, _scopes.under(scope))
    busy = sum(obs["busy_by_device"].values())
    if not seconds or busy <= 0:
        return None
    return 100.0 * seconds / busy
