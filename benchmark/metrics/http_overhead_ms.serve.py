"""Service: mean client latency (send -> reply) of the requests completed
in the window minus the engine's own mean submit -> retire time
(serving_request_seconds) over the same window: HTTP, JSON and threads."""

from benchmark.metrics import _hist


def read(obs):
    engine = _hist.mean_ms(obs, "serving_request_seconds")
    lat = obs.get("client_latency_s_in_window")
    if engine is None or not lat:
        return None
    return 1000.0 * sum(lat) / len(lat) - engine
