"""The engine thread's calls into device programs: the
``serving.engine.launch`` regions (stat ``program``; ``compiles`` where the
call compiled or loaded), each the innermost region of its phase. A call
returns when the program is enqueued, so it blocks behind a full device
queue: what is left of a turn without them, ``idle`` and ``fetch`` is the
host's own Python. A capture of a program without the region (the parent
commit's) gives None."""

from benchmark.metrics import _spans

LAUNCH = "serving.engine.launch"
ADMIT = "serving.engine.admit"
WAITS = ("serving.engine.idle", "serving.engine.fetch")


def captured(obs):
    """(all ``serving.*`` spans, the turns wholly inside the traced window),
    or None."""
    if obs["kind"] != "serve" or "trace_window" not in obs:
        return None
    spans = _spans.serving_spans(obs)
    turns = _spans.inside(spans, obs["trace_window"], _spans.TURN)
    if not turns or not any(s.name == LAUNCH for s in spans):
        return None
    return spans, turns


def ms_per_turn(obs, names, own):
    """Mean over the turns of the regions of ``names`` inside each (``own``:
    of the turn's time without them), in ms."""
    got = captured(obs)
    if got is None:
        return None
    spans, turns = got
    inside = [sum(c.dur_ns for c in _spans.children(spans, t, names)) for t in turns]
    total = sum(t.dur_ns - x for t, x in zip(turns, inside)) if own else sum(inside)
    return total / len(turns) / 1e6
