"""Flash attention forward: the least time the chip could take for its
shapes (max of FLOPs / peak and bytes / bandwidth) over its device time in
the trace, all calls of the traced steps (remat's second forward too)."""

from benchmark.metrics import _flash


def read(obs):
    return _flash.share(obs, "fwd")
