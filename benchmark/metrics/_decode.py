"""Device time of one single-token decode step: executions of the engine's
decode program (``jit_step``, a scan of ``chunk`` steps) wholly inside the
traced window, mean duration over ``chunk``."""

from benchmark import trace_reduce as tr


def step_seconds(obs):
    if obs["kind"] != "serve" or "trace" not in obs:
        return None
    evs = tr.module_events(obs["trace"], obs["trace_window"], obs["program_name"])
    if not evs:
        return None
    return sum(e.dur_ns for e in evs) / len(evs) / 1e9 / obs["decode_chunk"]
