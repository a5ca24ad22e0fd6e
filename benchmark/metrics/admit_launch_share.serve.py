"""Engine: the share of admission spent inside calls of device programs:
the ``serving.engine.launch`` regions inside the ``serving.engine.admit``
regions wholly in the traced window, over those regions. The rest of a wave
is the host's own work (keys, padding, transfers, block grants)."""

from benchmark.metrics import _launches, _spans


def read(obs):
    got = _launches.captured(obs)
    if got is None:
        return None
    spans, _ = got
    admits = _spans.inside(spans, obs["trace_window"], _launches.ADMIT)
    if not admits:
        return None
    launched = sum(c.dur_ns for a in admits
                   for c in _spans.children(spans, a, (_launches.LAUNCH,)))
    return 100.0 * launched / sum(a.dur_ns for a in admits)
