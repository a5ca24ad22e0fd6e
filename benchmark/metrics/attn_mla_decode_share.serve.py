"""Model step, serving: the share of the device's busy time in the traced
window spent under ``attn_mla_decode`` (the queries folded into latent
space, the absorbed kernel over each slot's own pages, the context mapped
out of latent space, in the decode steps)."""

from benchmark.metrics import _sarvam


def read(obs):
    return _sarvam.busy_share(obs, "attn_mla_decode")
