"""Model FLOPs of the tokens prefilled and decoded for the requests that
completed in the window (from shapes) over window x the bf16 peak."""

from benchmark import flops
from benchmark.peaks import peaks_for


def read(obs):
    if obs["kind"] != "serve" or not obs["prompt_len_in_window"]:
        return None
    s = obs["sizes"]
    dims = (s["n_layer"], s["n_embd"], s["n_inner"], s["vocab_size"])
    work = sum(flops.prefill_flops(*dims, p) + flops.decode_flops(*dims, p, n)
               for p, n in zip(obs["prompt_len_in_window"], obs["n_out_in_window"]))
    peak = peaks_for(obs["device_kind"])["bf16_flops_per_s"]
    return 100.0 * work / (obs["window_s"] * obs["chips"] * peak)
