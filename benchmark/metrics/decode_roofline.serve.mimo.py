"""The bytes a decode step has to read (attention, dense, router and head
weights once; the three matrices of every expert touched, from the
``experts_touched`` counter of the decode chunks delivered in the traced
window; the live keys and values of each kind, from the blocks in use by
kind sampled through it) over the peak bandwidth, against the decode
step's device time. Memory bounds it."""

from benchmark import moe_cost
from benchmark.metrics import _decode, _mimo
from benchmark.peaks import peaks_for


def read(obs):
    if not _mimo.has_sizes(obs):
        return None
    step = _decode.step_seconds(obs)
    chunks = _mimo.deliveries(obs, kinds=("chunk",))
    full, window = obs.get("kv_blocks_used_full"), obs.get("kv_blocks_used_window")
    if step is None or not chunks or not full or not window:
        return None
    touched = (sum(int(c["experts_touched"]) for c in chunks)
               / (len(chunks) * obs["decode_chunk"]))          # per step, all layers
    bt = obs["kv_block_t"]
    need = moe_cost.decode_step_bytes(obs["sizes"], bt * sum(full) / len(full),
                                      bt * sum(window) / len(window), touched)
    return 100.0 * need / peaks_for(obs["device_kind"])["hbm_bytes_per_s"] / step
