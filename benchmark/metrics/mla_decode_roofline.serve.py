"""Kernels: the absorbed latent decode attention (``latent_decode_attention``,
once a layer and step) against its roofline, inside the decode dispatches of
the traced window. Work: the latent pages the steps fetch (the deliveries'
``latent_pages_read``, counted on the device a step and layer, mean a step
times the steps executed and the layers), a page ``block_t`` rows of 640 as
the arena lies (20,480 B); a head's scores over the 576 columns and its
context over the 512 of each position. max(FLOPs / peak, bytes /
bandwidth) over the kernels' device time."""

from benchmark import flops, sarvam_cost
from benchmark.metrics import _sarvam
from benchmark.peaks import peaks_for


def read(obs):
    runs, pages = _sarvam.runs(obs), _sarvam.per_step(obs, "latent_pages_read")
    seconds = _sarvam.kernel_seconds(obs, runs, _sarvam.DECODE_KERNEL) if runs and pages else 0.0
    if not seconds:
        return None
    steps = len(runs) * obs["decode_chunk"] * len(obs["sizes"]["moe_layers"])
    cost = sarvam_cost.mla_decode_cost(obs["sizes"], steps * pages, obs["kv_block_t"])
    return 100.0 * flops.roofline_seconds(cost, peaks_for(obs["device_kind"])) / seconds
