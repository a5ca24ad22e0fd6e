"""Training loop: the share of the device's busy time spent on
rematerialized forwards: operations whose scope path carries JAX's
``rematted_computation`` marker (``jax.checkpoint``'s recomputation in the
backward pass: the scanned blocks' second forward, and the blockwise
loss's recomputed chunks)."""

from benchmark.metrics import _scopes


def read(obs):
    return _scopes.share_of_busy(obs, "rematted_computation")
