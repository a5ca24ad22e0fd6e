"""The engine's own per-request records: ``serving.request`` spans of the
program's ``runtime.tracing.TRACER`` (one per request, submit() ->
retirement, with the events ``enqueued``, ``dequeued``, ``admitted``,
``prefill_done``, ``first_token``, ``retired`` stamped by
``time.time_ns()``), read from its ring of finished spans after the run.
The measured requests are the last ``requests_measured`` by start: the
lead-in's requests start before them. A difference of two events lies
inside one clock, so no clock is mapped. ``obs["request_spans"]``, where a
test provides it, is taken first."""


def measured(obs):
    """The measured requests' spans, or None where the ring holds fewer."""
    if obs["kind"] != "serve":
        return None
    spans = obs.get("request_spans")
    if spans is None:
        from kubeflow_tpu.runtime.tracing import TRACER

        spans = TRACER.finished_spans("serving.request")
    want = obs["requests_measured"]
    if want <= 0 or len(spans) < want:
        return None
    return sorted(spans, key=lambda s: s.start_ns)[-want:]


def mean_gap_ms(obs, first, second):
    """Mean of ``second - first`` over the measured requests that carry
    both events (a request that failed before ``second`` carries none)."""
    spans = measured(obs)
    if spans is None:
        return None
    gaps = []
    for span in spans:
        at = {e["name"]: e["timeUnixNano"] for e in span.events}
        if first in at and second in at:
            gaps.append((at[second] - at[first]) / 1e6)
    return sum(gaps) / len(gaps) if gaps else None
