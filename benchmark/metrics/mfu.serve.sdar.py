"""Model FLOPs of the requests that completed in the window: the prefill of
each prompt's whole blocks and ``denoise_steps + 1`` forward passes a block
over the blocks that hold its new tokens (``benchmark/sdar_cost.py``:
projections, a token's 8 experts, the head, attention over the keys a block
sees; what the program runs beyond or below that is not counted), over
window x the bf16 peak: the share of the whole step."""

from benchmark import sdar_cost
from benchmark.metrics import _sdar
from benchmark.peaks import peaks_for


def read(obs):
    if not _sdar.has_sizes(obs) or not obs.get("prompt_len_in_window"):
        return None
    s = obs["sizes"]
    work = sum(sdar_cost.prefill_flops(s, p) + sdar_cost.generate_flops(s, p, n)
               for p, n in zip(obs["prompt_len_in_window"], obs["n_out_in_window"]))
    peak = peaks_for(obs["device_kind"])["bf16_flops_per_s"]
    return 100.0 * work / (obs["window_s"] * obs["chips"] * peak)
