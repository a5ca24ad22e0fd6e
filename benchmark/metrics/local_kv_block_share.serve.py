"""Engine: the blocks the local kind of cache holds over its whole arena
(a ring for every slot: ``obs["local_ring_blocks"]``), mean over the
``serving.engine.dispatch`` regions of the traced window: what giving a
window back at roll-over, and slots that stand empty, leave idle."""

from benchmark.metrics import _eva


def read(obs):
    stats = _eva.dispatches(obs)
    if not stats or not obs.get("local_ring_blocks"):
        return None
    held = sum(int(d["local_blocks"]) for d in stats) / len(stats)
    return 100.0 * held / obs["local_ring_blocks"]
