"""Model FLOPs of the tokens prefilled and decoded for the requests that
completed in the window (projections, the Mamba states in the recurrent
form, attention, routers, latent projections, shared experts and head from
shapes: ``nemotron_cost``), plus the routed products of the assignments that
landed on HELD experts in the window (the program's counter
``serving_moe_assignments_total{held="true"}``), over window x the bf16
peak: the share of the whole step."""

from benchmark import nemotron_cost
from benchmark.metrics import _nemotron
from benchmark.peaks import peaks_for


def read(obs):
    if not _nemotron.has_sizes(obs) or not obs.get("prompt_len_in_window") \
            or "moe_assignments_held" not in obs:
        return None
    s = obs["sizes"]
    work = sum(nemotron_cost.prefill_flops(s, p) + nemotron_cost.decode_flops(s, p, n)
               for p, n in zip(obs["prompt_len_in_window"], obs["n_out_in_window"]))
    work += nemotron_cost.expert_flops(s, obs["moe_assignments_held"])
    peak = peaks_for(obs["device_kind"])["bf16_flops_per_s"]
    return 100.0 * work / (obs["window_s"] * obs["chips"] * peak)
