"""Model step, serving: the share of the decode dispatches' device time in
the traced window spent under ``lm_head`` and ``unmask``: the head over
151,936 ids for ``slots x block_len`` rows, the log-sum-exp and the
confidence, the choice of the positions to reveal and the slots' next
block state."""

from benchmark.metrics import _sdar


def read(obs):
    runs = _sdar.step_runs(obs)
    seconds = _sdar.scope_seconds(obs, runs, "lm_head", "unmask") if runs else 0.0
    if not seconds:
        return None
    return 100.0 * seconds / _sdar.seconds_of(runs)
