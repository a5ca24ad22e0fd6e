"""All tokens of all steps completed in the window over the window's whole
time (host clock, closed by block_until_ready on the last step); for a
cell on several chips, of the whole host."""


def read(obs):
    return obs["tokens_per_s"] if obs["kind"] == "train" else None
