"""The one traffic generator: reads a mix (``benchmark/traffic/<mix>.json``)
and a seed, returns the inputs. A mix is data; a new mix needs no code.

Two kinds:

``closed_loop_batches`` — a training feed. ``shape`` is the batch as the
step takes it (``[batch, seq]`` or ``[microbatches, rows, seq]``); every
step gets a fresh batch of token ids drawn uniformly below ``vocab`` from
``(seed, step)``, so all rows differ and the same seed gives the same feed.

``open_loop`` — serving arrivals on a schedule, whether or not earlier
requests have finished. ``rate_rps`` requests a second for
``lead_in_s + seconds``; the lead-in fills the engine before the window
opens and is not measured. Gaps are exponential (Poisson arrivals) and
prompt lengths follow ``prompt_len`` (``lognormal``: median, sigma, min,
max; ``uniform``: min, max; ``fixed``: value). Both are STRATIFIED: the n
requests take the n mid-quantiles of their distribution, shuffled by the
mix's own ``order`` number, NOT by the seed: every seed sends the same
arrival times and prompt lengths in the same order and draws only the token
ids (and the weights). With the order drawn from the seed, six seeds of the
chat mix spread the 95th percentile by a quarter of its median (PR 24) —
which long prompts collide is most of a tail — and no bound could hold. Optional ``burst``: every ``every_s`` seconds, ``size``
requests arrive together (their gaps collapse to zero). Optional
``shared_prefix``: a ``share`` of the requests start with one of ``groups``
fixed prefixes of ``length`` tokens (cut to the prompt's own length).
Token ids are uniform in [1, vocab).
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Any, Dict, List, NamedTuple

import numpy as np

HERE = Path(__file__).resolve().parent


def load_mix(name: str, overrides: Dict[str, Any] | None = None) -> Dict[str, Any]:
    mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    mix.update(overrides or {})
    return mix


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


# -- closed loop -------------------------------------------------------------

def batch(mix: Dict[str, Any], vocab: int, seed: int, step: int) -> np.ndarray:
    return _rng(seed, 1, step).integers(0, vocab, size=tuple(mix["shape"]),
                                        dtype=np.int32)


def tokens_per_batch(mix: Dict[str, Any]) -> int:
    return int(np.prod(mix["shape"]))


# -- open loop ---------------------------------------------------------------

class Arrival(NamedTuple):
    due_s: float            # relative to the window's start; < 0 = lead-in
    prompt: List[int]


def _length_quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    kind = spec["kind"]
    if kind == "fixed":
        out = np.full(n, spec["value"], float)
    elif kind == "uniform":
        out = spec["min"] + u * (spec["max"] - spec["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        out = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    else:
        raise ValueError(f"prompt_len kind {kind!r}")
    return np.clip(np.rint(out), spec.get("min", 1), spec.get("max", 1 << 30)).astype(int)


def arrivals(mix: Dict[str, Any], vocab: int, seed: int, seconds: float) -> List[Arrival]:
    lead = float(mix.get("lead_in_s", 0.0))
    span = lead + seconds
    n = max(1, int(round(mix["rate_rps"] * span)))
    rng = _rng(seed, 2)                       # token ids, prefix groups
    order = _rng(int(mix.get("order", 0)), 4)  # which request gets which size
    # exponential gaps at mid-quantiles, rescaled so that they fill the span
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= span / gaps.sum()
    gaps = gaps[order.permutation(n)]
    due = np.cumsum(gaps) - gaps[0] - lead
    burst = mix.get("burst")
    if burst:
        # every `every_s` seconds the next `size` requests arrive together
        every, size = float(burst["every_s"]), int(burst["size"])
        t = -lead + every
        while t < seconds:
            idx = np.searchsorted(due, t)
            due[idx:idx + size] = t
            t += every
        due = np.sort(due)
    lengths = _length_quantiles(mix["prompt_len"], n)[order.permutation(n)]
    shared = mix.get("shared_prefix")
    prefixes = None
    if shared:
        prefixes = _rng(seed, 3).integers(
            1, vocab, size=(int(shared["groups"]), int(shared["length"])))
        in_group = rng.random(n) < float(shared["share"])
        group = rng.integers(0, int(shared["groups"]), size=n)
    out = []
    for i in range(n):
        prompt = rng.integers(1, vocab, size=int(lengths[i]))
        if prefixes is not None and in_group[i]:
            k = min(len(prompt), prefixes.shape[1])
            prompt[:k] = prefixes[group[i], :k]
        out.append(Arrival(float(due[i]), prompt.tolist()))
    return out
