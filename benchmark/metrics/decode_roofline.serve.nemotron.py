"""The bytes one decode step has to move (every weight a token multiplies
by once; the two matrices of every expert TOUCHED, from the deliveries'
``experts_touched``; the live rows' Mamba states read and written with
their convolution windows, from ``ssm_rows``; the live rows' KV pages,
whole, from ``kv_pages_read``; all counted on the device a step) over the
peak bandwidth, against a step's device time: the decode dispatch's over
its ``chunk`` steps. Memory bounds it."""

from benchmark import nemotron_cost
from benchmark.metrics import _nemotron
from benchmark.peaks import peaks_for


def read(obs):
    runs = _nemotron.runs(obs)
    touched = _nemotron.per_step(obs, "experts_touched")
    if not runs or touched is None:
        return None
    step_s = _nemotron.seconds_of(runs) / len(runs) / obs["decode_chunk"]
    need = nemotron_cost.decode_step_bytes(
        obs["sizes"], touched, _nemotron.per_step(obs, "ssm_rows"),
        _nemotron.per_step(obs, "kv_pages_read"), obs["kv_block_t"])
    return 100.0 * need / peaks_for(obs["device_kind"])["hbm_bytes_per_s"] / step_s
