"""Runner ``eva_serve``: the EvaByte configuration behind the same
``ModelServer`` + ``GenerativeModel`` + ``ContinuousBatcher`` as
``gpt_serve`` and ``mimo_serve``, driven by the same open-loop ``Drive``
over loopback HTTP.

What differs is the model family (``EvaConfig``: EVA attention over a paged
cache of a local and a summary kind), the weights (``weights_eva``: a
tensor at a time) and the reference the served bytes are held to
(``reference/evabyte.py``, one sequence and one layer at a time).
``correct`` compares as the other serve cells do: after the window, 16
finished requests drawn from the seed, the longest among them, go through
the reference with their served tokens; ``served_logit_gap_sd`` is the
widest gap by which a served token's reference logit lies under the
reference's best, in standard deviations of that position's logits.
"""

from __future__ import annotations

import gc
from typing import Any, Dict, List

import numpy as np

from .. import harness, traffic, weights_eva
from ..reference import evabyte as ref
from .gpt_serve import MODEL, Drive, malformed, sample_requests

#: the readings a limit's upper end is set from: (name, cast, fault)
VARIANTS = (("control_fp8", ref.fp8_cast, None), ("fault_no_remote", None, "no_remote"),
            ("fault_mean_pool", None, "mean_pool"),
            ("fault_stale_rollover", None, "stale_rollover"))
#: a sequence is padded for the reference to the next of these numbers of
#: windows: one compilation a length and variant, so few of them
WINDOWS = (1, 2, 3, 4, 6, 8, 12, 16)


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys under the names the program, the reference and
    ``eva_cost`` use."""
    c = config
    return {
        "vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"],
        "head_dim": c["hidden_size"] // c["num_attention_heads"],
        "d_ff": c["intermediate_size"], "n_layers": c["num_hidden_layers"],
        "window": c["window_size"], "chunk_size": c["chunk_size"],
        "rope_theta": float(c["rope_theta"]), "norm_eps": c["rms_norm_eps"],
        "max_seq": c["max_position_embeddings"],
    }


class Server:
    """The served model, warmed for a mix's shapes."""

    def __init__(self, cell: harness.Cell, devices: List[Any]):
        from kubeflow_tpu.models.evabyte import EvaConfig
        from kubeflow_tpu.serving.server import GenerativeModel, ModelServer

        self.sizes = s = sizes_of(cell.config)
        self.deploy = d = cell.deploy
        self.mix = cell.mix
        self.mcfg = EvaConfig(**s)
        self.new_tokens = int(d["max_new_tokens"])
        self.model = GenerativeModel(
            name=MODEL, apply_fn=None, params=weights_eva.program_tree(cell.seed, s),
            cfg=self.mcfg, max_new_tokens=self.new_tokens, slots=int(d["slots"]),
            kv_blocks=int(d["kv_blocks"]),
            kv_block_t=int(d["kv_block_t"]), prefill_chunk=int(d["prefill_chunk"]))
        self.server = ModelServer()
        self.server.add(self.model)
        self.httpd = self.server.serve(0)
        self.port = self.httpd.port
        self.engine = self.model._continuous_engine()
        harness.note("weights made, server up, engine built")
        self.warm()
        harness.note("every shape of the mix warmed")

    def drop_state(self) -> None:
        """Frees the weights' 3.2 GB and the arenas' 9.7 GB while the engine
        is idle (limit readings: the reference needs the room between two
        windows; every slot is free then, and an activation sets a slot's
        cursor and overwrites what it reads)."""
        self.model.params = self.engine.params = self.engine.cache = None
        gc.collect()

    def load_weights(self, seed: int) -> None:
        """Another seed's weights into the live engine, and fresh arenas
        (limit readings): the programs take both as arguments."""
        self.drop_state()
        self.model.params = self.engine.params = weights_eva.program_tree(seed, self.sizes)
        self.engine.cache = self.engine.family.fresh_cache()

    def warm(self) -> None:
        """The one chunk shape at every view width, and the decode program
        at every view width: one dummy prompt of the mix's longest length
        passes through all of them."""
        spec = self.mix["prompt_len"]
        hi = int(spec.get("max", spec.get("value", 1)))
        self.engine.prewarm(hi, group_sizes=[1])

    def close(self) -> None:
        self.httpd.close()
        self.server.close()
        self.model.close()
        self.model = self.engine = self.server = None
        gc.collect()


class EvaDrive(Drive):
    """``Drive`` plus what the cell's readers need to know of the engine."""

    def observations(self) -> Dict[str, Any]:
        obs = super().observations()
        obs["prefill_program_name"] = "prefill_chunk"
        obs["local_ring_blocks"] = int(self.server.engine.kv.rings.alloc.n_blocks)
        return obs


# -- correct ---------------------------------------------------------------------

def reference_gaps(drive: Drive, picks: List[int], seed: int, sizes: Dict[str, Any],
                   variants=()):
    """The plain reference over each sampled prompt with its served tokens,
    a layer at a time (each layer's float32 weights made, used for every
    sampled sequence, and dropped) and a VARIANT at a time (16 sequences of
    up to 32,768 positions are 2.6 GB of float32 a pass: not five at once).
    Returns (gaps of the served tokens [len(picks), new], {name: gaps of the
    tokens a variant of the reference puts first at the same positions}); a
    variant is (name, cast, fault): the reference at a lower precision, or
    computing a wrong model."""
    import jax
    import jax.numpy as jnp

    fs = ref.frozen(sizes)
    new, window = drive.server.new_tokens, sizes["window"]
    seqs = [np.asarray(drive.replies[i], np.int32) for i in picks]
    at = [len(drive.arrivals[i].prompt) - 1 + np.arange(new) for i in picks]
    top = weights_eva.top_canonical(seed, sizes)

    def padded(q):
        n = next(w * window for w in WINDOWS if w * window >= len(q))
        return jnp.asarray(np.pad(q, (0, n - len(q))))

    def hidden(cast, fault):
        """The last layer's output at the served positions, a sequence."""
        xs = [top["embedding"][padded(q)] for q in seqs]
        for i in range(sizes["n_layers"]):
            w = weights_eva.layer_canonical(seed, sizes, i)
            xs = [ref.block_jit(fs, w, x, cast=cast, fault=fault) for x in xs]
            del w
        out = [x[rows] for x, rows in zip(xs, at)]
        harness.note(f"reference pass over {len(seqs)} sequences, {sum(map(len, xs))} padded "
                     f"positions ({fault or ('float8' if cast else 'plain')})")
        return out

    head = jax.jit(lambda t, h, c: ref.logits_at(sizes, t, h, c), static_argnums=2)
    logits = [head(top, h, None) for h in hidden(None, None)]
    served = np.stack([np.asarray(ref.gaps_under_best(lg, jnp.asarray(q[rows + 1])))
                       for lg, q, rows in zip(logits, seqs, at)])
    other = {}
    for name, cast, fault in variants:
        best = [jnp.argmax(head(top, h, cast), axis=-1) for h in hidden(cast, fault)]
        other[name] = np.stack([np.asarray(ref.gaps_under_best(lg, b))
                                for lg, b in zip(logits, best)])
    return served, other


def run(cell: harness.Cell, devices: List[Any], t0: float) -> harness.Outcome:
    server = Server(cell, devices)
    sizes = server.sizes
    arrivals = traffic.arrivals(cell.mix, sizes["vocab_size"], cell.seed, cell.seconds)
    profiler = harness.Profiler(cell) if cell.trace else None
    drive = EvaDrive(server, arrivals, cell.seconds, profiler)
    t_open = drive.run()
    setup_s = t_open - t0
    peak = harness.allocator_peak(devices)
    obs = drive.observations()
    obs.update(sizes=sizes, chips=cell.chips, device_kind=devices[0].device_kind)
    bad = malformed(drive, sizes["vocab_size"])
    picks = sample_requests(drive, cell.seed, int(cell.params.get("check_requests", 16)))
    server.close()
    checks = [("malformed_replies", float(bad), float(cell.limits["malformed_replies"]))]
    if picks:
        gaps, _ = reference_gaps(drive, picks, cell.seed, sizes)
        worst = float(np.where(np.isfinite(gaps), gaps, np.inf).max())
    else:
        worst = float("inf")
    checks.append(("served_logit_gap_sd", worst, float(cell.limits["served_logit_gap_sd"])))
    return harness.Outcome(
        obs=obs, attempted=obs["requests_measured"], failed=obs["requests_failed"],
        setup_s=setup_s, checks=checks, memory_peak_bytes=peak,
        trace_dir=profiler.dir if profiler else None,
        extra={"requests_in_window": obs["requests_in_window"],
               "checked_requests": len(picks)})


def limit_readings(cell: harness.Cell, devices: List[Any], seeds: List[int],
                   control_seeds: int):
    """For ``benchmark/limits.py``: one warmed server, per seed that seed's
    weights and a short window at the cell's own load; the served tokens'
    widest gap (the lower reading), and for the first seeds the control's
    (the tokens the reference at fp8 puts first, at the same positions) and
    each fault's (the tokens a reference without summaries, with plain-mean
    pooling, or with a stale roll-over puts first)."""
    server = Server(cell, devices)
    sizes, vocab = server.sizes, server.sizes["vocab_size"]
    count = int(cell.params.get("check_requests", 16))
    for n, seed in enumerate(seeds):
        if n:
            server.load_weights(seed)
        drive = EvaDrive(server, traffic.arrivals(cell.mix, vocab, seed, cell.seconds),
                         cell.seconds)
        drive.run()
        picks = sample_requests(drive, seed, count)
        server.drop_state()
        row = {"seed": seed, "requests": int(drive.ok.sum()), "checked": len(picks),
               "longest": max(len(drive.replies[i]) for i in picks)}
        served, wrong = reference_gaps(drive, picks, seed, sizes,
                                       VARIANTS if n < control_seeds else ())
        for name, gaps in wrong.items():
            yield {**row, "who": name, "served_logit_gap_sd": float(gaps.max()),
                   "tokens_off_the_reference_best": int((gaps > 0).sum())}
        yield {**row, "who": "program", "served_logit_gap_sd": float(served.max()),
               "malformed_replies": malformed(drive, vocab),
               "tokens_off_the_reference_best": int((served > 0).sum())}
    server.close()
