"""Engine: the share of computed decode rows that reach a caller. Every
dispatch computes all slots x chunk rows, live or not; a
``serving.engine.deliver`` span carries ``rows`` (of the event consumed)
and ``tokens`` (usable tokens appended to live requests). Sum of tokens
over sum of rows of the decode events (``chunk``, ``spec``) delivered in
the traced window; an admission's first tokens (``first``) are prefill's."""

from benchmark.metrics import _spans


def read(obs):
    if obs["kind"] != "serve" or "trace_window" not in obs:
        return None
    spans = _spans.inside(_spans.serving_spans(obs), obs["trace_window"],
                          "serving.engine.deliver")
    decode = [s.stats for s in spans if s.stats.get("kind") != "first"]
    rows = sum(int(s.get("rows", 0)) for s in decode)
    if rows <= 0:
        return None
    return 100.0 * sum(int(s.get("tokens", 0)) for s in decode) / rows
