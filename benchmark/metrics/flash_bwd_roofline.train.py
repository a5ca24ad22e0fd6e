"""Flash attention backward, the dq and dkv kernels together: the least
time the chip could take for the five matmuls the algorithm needs (the two
kernels do seven) over their device time in the trace."""

from benchmark.metrics import _flash


def read(obs):
    return _flash.share(obs, "bwd")
