"""The bytes a decode step has to read (every matmul weight once; in every
layer the live rows' local keys and values and their visible summaries,
from ``local_blocks_read`` and ``summary_blocks_read`` of the traced
window's dispatches, whole pages) over the peak bandwidth, against the
decode step's device time. Memory bounds it."""

from benchmark import eva_cost
from benchmark.metrics import _decode, _eva
from benchmark.peaks import peaks_for


def read(obs):
    if not _eva.has_sizes(obs):
        return None
    step, rows = _decode.step_seconds(obs), _eva.mean_rows_read(obs)
    if step is None or rows is None:
        return None
    need = eva_cost.decode_step_bytes(obs["sizes"], *rows)
    return 100.0 * need / peaks_for(obs["device_kind"])["hbm_bytes_per_s"] / step
