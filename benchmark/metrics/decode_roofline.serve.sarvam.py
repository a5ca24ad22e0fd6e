"""The bytes one decode step has to read (every weight a token multiplies
by once; the three matrices of every expert TOUCHED, from the deliveries'
``experts_touched``; the live rows' latent pages, whole, from their
``latent_pages_read``, both counted on the device a step) over the peak
bandwidth, against a step's device time: the decode dispatch's over its
``chunk`` steps. Memory bounds it."""

from benchmark import sarvam_cost
from benchmark.metrics import _sarvam
from benchmark.peaks import peaks_for


def read(obs):
    runs = _sarvam.runs(obs)
    touched = _sarvam.per_step(obs, "experts_touched")
    pages = _sarvam.per_step(obs, "latent_pages_read")
    if not runs or touched is None:
        return None
    step_s = _sarvam.seconds_of(runs) / len(runs) / obs["decode_chunk"]
    need = sarvam_cost.decode_step_bytes(obs["sizes"], touched, pages, obs["kv_block_t"])
    return 100.0 * need / peaks_for(obs["device_kind"])["hbm_bytes_per_s"] / step_s
