"""Model FLOPs of the tokens prefilled and decoded for the requests that
completed in the window (attention, dense, router and head from shapes),
plus the expert products of the assignments that landed on HELD experts in
the window (the program's counter ``serving_moe_assignments_total{held=
"true"}``: nobody here does the work of the experts held elsewhere), over
window x the bf16 peak."""

from benchmark import moe_cost
from benchmark.metrics import _mimo
from benchmark.peaks import peaks_for


def read(obs):
    if not _mimo.has_sizes(obs) or not obs["prompt_len_in_window"] \
            or "moe_assignments_held" not in obs:
        return None
    s = obs["sizes"]
    work = sum(moe_cost.prefill_flops(s, p) + moe_cost.decode_flops(s, p, n)
               for p, n in zip(obs["prompt_len_in_window"], obs["n_out_in_window"]))
    work += moe_cost.expert_flops(s, obs["moe_assignments_held"])
    peak = peaks_for(obs["device_kind"])["bf16_flops_per_s"]
    return 100.0 * work / (obs["window_s"] * obs["chips"] * peak)
