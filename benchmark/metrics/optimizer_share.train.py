"""Training loop: the share of the devices' busy time spent under the
``optimizer`` scope (the optimizer's update and the parameters' apply),
summed over the chips."""

from benchmark.metrics import _scopes


def read(obs):
    return _scopes.share_of_busy(obs, "optimizer")
