"""The bytes a decode step has to read (every matmul weight once in the
compute type, plus the keys and values of the tokens live in the arena,
from the arena's blocks in use sampled through the traced window) over the
peak bandwidth, against the decode step's device time. Memory bounds it."""

from benchmark import flops
from benchmark.metrics import _decode
from benchmark.peaks import peaks_for


def read(obs):
    step = _decode.step_seconds(obs)
    used = obs.get("kv_blocks_used")
    if step is None or not used:
        return None
    s = obs["sizes"]
    live = obs["kv_block_t"] * sum(used) / len(used)
    need = flops.decode_step_bytes(s["n_layer"], s["n_embd"], s["n_inner"],
                                   s["vocab_size"], live)
    return 100.0 * need / peaks_for(obs["device_kind"])["hbm_bytes_per_s"] / step
