"""Output tokens of all requests completed inside the window (lead-in
requests that finish in it too) over the window's whole time."""


def read(obs):
    if obs["kind"] != "serve":
        return None
    return obs["tokens_out_in_window"] / obs["window_s"]
