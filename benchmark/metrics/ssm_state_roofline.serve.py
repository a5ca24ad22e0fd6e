"""Kernels: the Mamba layers' decode update (``ssm_decode_update``, once a
Mamba layer and step) against its bytes, inside the decode dispatches of
the traced window. Work: the (live row, layer) states the steps read and
wrote (the deliveries' ``ssm_rows``, counted on the device a step, mean a
step times the steps executed), each the state read and written with its
inputs and output once (``nemotron_cost.ssm_state_bytes``), over the peak
bandwidth, against the kernels' device time. Memory bounds it."""

from benchmark import nemotron_cost
from benchmark.metrics import _nemotron
from benchmark.peaks import peaks_for


def read(obs):
    runs, rows = _nemotron.runs(obs), _nemotron.per_step(obs, "ssm_rows")
    seconds = _nemotron.kernel_seconds(obs, runs, _nemotron.STATE_KERNEL) if runs and rows else 0.0
    if not seconds:
        return None
    need = nemotron_cost.ssm_state_bytes(obs["sizes"], rows * len(runs) * obs["decode_chunk"])
    return 100.0 * need / peaks_for(obs["device_kind"])["hbm_bytes_per_s"] / seconds
