"""Model step, serving: the share of the device's busy time in the traced
window spent under ``attn_mla_prefill`` (the row's view gathered, the
latent expanded a key block at a time and attended, in the prefill chunks)."""

from benchmark.metrics import _sarvam


def read(obs):
    return _sarvam.busy_share(obs, "attn_mla_prefill")
