"""Model step, serving: the share of the decode and prefill programs'
device time in the traced window spent under ``mamba`` (the Mamba-2 mixers:
their projections in and out, the convolution, the scan or the decode
update, the gated norm)."""

from benchmark.metrics import _nemotron


def read(obs):
    runs = _nemotron.runs(obs) + _nemotron.runs(obs, "prefill_program_name")
    seconds = _nemotron.scope_seconds(obs, "mamba", runs) if runs else 0.0
    if not seconds:
        return None
    return 100.0 * seconds / _nemotron.seconds_of(runs)
