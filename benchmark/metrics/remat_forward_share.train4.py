"""Training loop, four chips: the share of the chips' busy time spent on
recomputation in the backward pass: operations whose scope path carries
JAX's ``rematted_computation`` marker. In ``parallel/composite.py`` that is
what the block's ``jax.checkpoint`` policy does not keep: the scores, the
mask and the softmax, the GELU's and LayerNorm's pieces, recomputed from
the saved matmul outputs (PR 31). A program without such a policy (the
parent) has no operation under the marker and the reader gives None."""

from benchmark.metrics import _scopes


def read(obs):
    return _scopes.share_of_busy(obs, "rematted_computation")
