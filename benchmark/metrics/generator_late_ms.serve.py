"""Mean of actual send time minus due time: how late the benchmark's own
load generator ran (a starved generator must not read as a fast server)."""


def read(obs):
    if obs["kind"] != "serve" or not obs["late_ms"]:
        return None
    return sum(obs["late_ms"]) / len(obs["late_ms"])
