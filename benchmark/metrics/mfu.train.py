"""Forward + backward model FLOPs per token (from shapes, nothing
recomputed) times tokens per second, over chips times the bf16 peak."""

from benchmark import flops
from benchmark.peaks import peaks_for


def read(obs):
    if obs["kind"] != "train":
        return None
    s = obs["sizes"]
    per_token = flops.train_flops_per_token(
        s["n_layer"], s["n_embd"], s["n_inner"], obs["vocab_run"], obs["seq"])
    peak = peaks_for(obs["device_kind"])["bf16_flops_per_s"]
    return 100.0 * per_token * obs["tokens_per_s"] / (obs["chips"] * peak)
