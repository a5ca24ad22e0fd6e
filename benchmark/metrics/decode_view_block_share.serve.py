"""Model step, serving: the share of the block table's columns that the
decode dispatches read. A paged dispatch hands the decode program the
table's first ``view_blocks`` columns (one of a few fixed widths covering
the longest granted row) out of ``max_blocks``; gather, conversion, scores,
softmax and context run over ``view_blocks x block_t`` positions a slot.
Both ride as stats of the ``serving.engine.dispatch`` span. Sum of
``view_blocks`` over sum of ``max_blocks`` of the dispatches in the traced
window: 100 is a step that reads every slot's whole view, and the live
positions of the rows together need far less than the longest row sets."""

from benchmark.metrics import _spans


def read(obs):
    if obs["kind"] != "serve" or "trace_window" not in obs:
        return None
    spans = _spans.inside(_spans.serving_spans(obs), obs["trace_window"],
                          "serving.engine.dispatch")
    whole = sum(int(s.stats.get("max_blocks", 0)) for s in spans)
    if whole <= 0:
        return None
    return 100.0 * sum(int(s.stats.get("view_blocks", 0)) for s in spans) / whole
