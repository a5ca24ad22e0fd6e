"""Model FLOPs of the bytes prefilled and decoded for the requests that
completed in the window (projections, SwiGLU and head from shapes; scores
and context over the keys each query SEES: its window's exact keys and one
summary a chunk of every earlier window), over window x the bf16 peak."""

from benchmark import eva_cost
from benchmark.metrics import _eva
from benchmark.peaks import peaks_for


def read(obs):
    if not _eva.has_sizes(obs) or not obs.get("prompt_len_in_window"):
        return None
    s = obs["sizes"]
    work = sum(eva_cost.prefill_flops(s, p) + eva_cost.decode_flops(s, p, n)
               for p, n in zip(obs["prompt_len_in_window"], obs["n_out_in_window"]))
    peak = peaks_for(obs["device_kind"])["bf16_flops_per_s"]
    return 100.0 * work / (obs["window_s"] * obs["chips"] * peak)
