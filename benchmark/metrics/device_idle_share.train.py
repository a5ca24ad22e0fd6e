"""1 - union of device-op intervals over the traced window, on the device
that was idle most (four chips: the worst of the four)."""


def read(obs):
    if obs["kind"] != "train" or "busy_by_device" not in obs:
        return None
    lo, hi = obs["trace_window"]
    return 100.0 * (1.0 - min(obs["busy_by_device"].values()) / ((hi - lo) / 1e9))
