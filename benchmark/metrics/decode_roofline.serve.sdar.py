"""The bytes one forward pass of the batch has to read (attention, router
and head weights once; the three matrices of every expert TOUCHED, from the
deliveries' ``experts_touched``; the live rows' pages, whole, from their
``blocks_read``, both counted on the device a pass) over the peak
bandwidth, against a pass's device time: the decode dispatch's over its
``chunk`` passes. Memory bounds it."""

from benchmark import sdar_cost
from benchmark.metrics import _sdar
from benchmark.peaks import peaks_for


def read(obs):
    runs = _sdar.step_runs(obs)
    touched, pages = _sdar.per_pass(obs, "experts_touched"), _sdar.per_pass(obs, "blocks_read")
    if not runs or touched is None:
        return None
    pass_s = _sdar.seconds_of(runs) / len(runs) / obs["decode_chunk"]
    need = sdar_cost.pass_bytes(obs["sizes"], touched, pages, obs["kv_block_t"])
    return 100.0 * need / peaks_for(obs["device_kind"])["hbm_bytes_per_s"] / pass_s
