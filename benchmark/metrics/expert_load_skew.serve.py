"""Engine: how unevenly the held experts are loaded. The busiest held
expert's assignments (``expert_tokens_max``, summed over layers and steps
on the device) times the experts held, over all assignments on held
experts (``expert_tokens``), of the events delivered in the traced window:
1 is an even load, the count of held experts everything on one."""

from benchmark.metrics import _mimo


def read(obs):
    stats = _mimo.deliveries(obs)
    total = sum(int(s["expert_tokens"]) for s in stats)
    if total <= 0:
        return None
    return (sum(int(s["expert_tokens_max"]) for s in stats)
            * obs["sizes"]["held_experts"] / total)
