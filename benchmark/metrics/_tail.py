"""A percentile of ms per output token over ALL requests due in the
window: from when each was due to its HTTP reply, over its output tokens;
a failed request counts with the whole wait over the tokens it was owed."""

import numpy as np


def percentile(obs, q):
    if obs["kind"] != "serve" or not obs["ms_per_token"]:
        return None
    return float(np.percentile(np.asarray(obs["ms_per_token"]), q))
