"""Process start to the first measured step or request (host clock)."""


def read(obs):
    return obs["setup_s"]
