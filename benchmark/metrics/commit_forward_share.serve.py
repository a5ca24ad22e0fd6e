"""Model step, serving: the commit passes' share of the live slots' forward
passes in the decode dispatches delivered in the traced window
(``commit_forwards`` over ``forwards`` of ``serving.engine.deliver``). One
pass is one device program for every slot, so a slot's pass is an equal
share of its device time whichever kind it is: 20 on the static schedule (4
denoising passes and the commit), and what merging the commit into the next
block's first pass would take away."""

from benchmark.metrics import _sdar


def read(obs):
    stats = _sdar.deliveries(obs)
    forwards = _sdar.total(stats, "forwards")
    if forwards <= 0 or not _sdar.step_runs(obs):
        return None
    return 100.0 * _sdar.total(stats, "commit_forwards") / forwards
