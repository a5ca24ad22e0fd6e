"""Engine: what one turn of the engine loop costs the host. Mean self time
of the ``serving.engine.turn`` spans wholly inside the traced window:
duration less the ``idle`` (blocked on an empty queue) and ``fetch``
(blocked on the device's tokens) spans inside it."""

from benchmark.metrics import _spans

WAITS = ("serving.engine.idle", "serving.engine.fetch")


def read(obs):
    if obs["kind"] != "serve" or "trace_window" not in obs:
        return None
    spans = _spans.serving_spans(obs)
    turns = _spans.inside(spans, obs["trace_window"], _spans.TURN)
    if not turns:
        return None
    own = [t.dur_ns - sum(c.dur_ns for c in _spans.children(spans, t, WAITS))
           for t in turns]
    return sum(own) / len(own) / 1e6
