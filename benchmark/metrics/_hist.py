"""Mean of one of the program's histograms over the window: delta of its
sum over delta of its count, between the window's opening and its close."""


def mean_ms(obs, name):
    if obs["kind"] != "serve":
        return None
    h = obs["histograms"]
    count = h.get(f"{name}_count", 0.0)
    if count <= 0:
        return None
    return 1000.0 * h[f"{name}_sum"] / count
