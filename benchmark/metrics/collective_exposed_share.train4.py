"""Collective time during which no compute ran on that device, over the
traced window, on the device where it was largest. Nothing to read where
the trace holds no collective."""

from benchmark import trace_reduce as tr


def read(obs):
    if obs["kind"] != "train" or "trace" not in obs:
        return None
    trace, window = obs["trace"], obs["trace_window"]
    if not tr.kernel_events(trace, (float("-inf"), float("inf")), tr.is_collective) \
            and not any(tr.is_collective(e.name)
                        for evs in trace.device_async.values() for e in evs):
        return None
    exposed = tr.exposed_collective_seconds(trace, window)
    return 100.0 * max(exposed.values()) / ((window[1] - window[0]) / 1e9)
