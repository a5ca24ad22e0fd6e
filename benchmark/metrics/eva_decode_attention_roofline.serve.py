"""Kernels: the decode attention (``paged_decode_attention``, called once
a kind of cache and layer in every step) against its roofline, inside the
decode program's executions of the traced window. Work: the pages of both
kinds the dispatches' last steps fetch (mean over the window's dispatches;
a page is ``block_t`` rows of one key and one value, 262,144 B at the
cell's widths), a layer and step of every execution. max(FLOPs / peak,
bytes / bandwidth) over the kernels' device time; the bytes bound it."""

from benchmark import eva_cost, flops, trace_reduce as tr
from benchmark.metrics import _eva
from benchmark.peaks import peaks_for


def read(obs):
    rows = _eva.mean_rows_read(obs)
    if rows is None or "trace" not in obs:
        return None
    runs = tr.module_events(obs["trace"], obs["trace_window"], obs["program_name"])
    seconds = _eva.kernel_seconds(obs, runs) if runs else 0.0
    if not seconds:
        return None
    calls = len(runs) * obs["decode_chunk"] * obs["sizes"]["n_layers"]
    cost = eva_cost.decode_attention_cost(obs["sizes"], calls * sum(rows))
    return 100.0 * flops.roofline_seconds(cost, peaks_for(obs["device_kind"])) / seconds
