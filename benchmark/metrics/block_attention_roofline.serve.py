"""Kernels: the block attention (``paged_decode_attention`` at ``block_len x
heads`` query rows a slot, once a layer and pass) against its roofline,
inside the decode dispatches of the traced window. Work: the pages the
passes fetch (the deliveries' ``blocks_read``, counted on the device a pass
and layer, mean a pass times the passes executed), a page ``block_t``
positions' keys and values (32,768 B at the cell's widths). max(FLOPs /
peak, bytes / bandwidth) over the kernels' device time; the bytes bound it."""

from benchmark import flops, sdar_cost
from benchmark.metrics import _sdar
from benchmark.peaks import peaks_for


def read(obs):
    runs, pages = _sdar.step_runs(obs), _sdar.per_pass(obs, "blocks_read")
    seconds = _sdar.kernel_seconds(obs, runs, _sdar.KERNEL) if runs and pages else 0.0
    if not seconds:
        return None
    calls = len(runs) * obs["decode_chunk"] * obs["sizes"]["n_layers"]
    cost = sdar_cost.block_attention_cost(obs["sizes"], calls * pages, obs["kv_block_t"])
    return 100.0 * flops.roofline_seconds(cost, peaks_for(obs["device_kind"])) / seconds
