"""Runner ``mimo_serve``: the MiMo configuration behind the same
``ModelServer`` + ``GenerativeModel`` + ``ContinuousBatcher`` as
``gpt_serve``, driven by the same open-loop ``Drive`` over loopback HTTP.

What differs is the model family (``MimoConfig``: window and full
attention in one paged cache of two kinds, an expert layer that holds a
share of the experts), the weights (``weights_mimo``: a layer at a time)
and the reference the served tokens are held to (``reference/mimo.py``,
one sequence and one layer at a time). ``correct`` compares as the chat
cell does: after the window, 16 finished requests drawn from the seed, the
longest among them, go through the reference with their served tokens;
``served_logit_gap_sd`` is the widest gap by which a served token's
reference logit lies under the reference's best, in standard deviations of
that position's logits.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Any, Dict, List

import numpy as np

from .. import harness, traffic, weights_mimo
from ..reference import mimo as ref
from .gpt_serve import MODEL, Drive, malformed, sample_requests

#: the readings a limit's upper end is set from: (name, cast, fault)
VARIANTS = (("control_fp8", ref.fp8_cast, None), ("fault_no_window", None, "no_window"),
            ("fault_no_sink", None, "no_sink"), ("fault_top7", None, "top7"))


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys under the names the program, the reference and
    ``moe_cost`` use."""
    c = config
    return {
        "vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"], "qk_dim": c["head_dim"],
        "v_dim": c["v_head_dim"], "kv_heads_full": c["num_key_value_heads"],
        "kv_heads_window": c["swa_num_key_value_heads"], "window": c["sliding_window"],
        "rotary_dim": c["rotary_dim_run"], "rope_theta_full": float(c["rope_theta"]),
        "rope_theta_window": float(c["swa_rope_theta"]),
        "value_scale": c["attention_value_scale"],
        "layer_kinds": list(c["hybrid_layer_pattern"]),
        "moe_layers": list(c["moe_layer_freq"]),
        "d_ff_dense": c["intermediate_size"], "d_ff_expert": c["moe_intermediate_size"],
        "n_experts": c["n_routed_experts_published"], "held_experts": c["n_routed_experts"],
        "experts_per_token": c["num_experts_per_tok"], "norm_eps": c["layernorm_epsilon"],
        "max_seq": c["max_position_embeddings"],
    }


class Server:
    """The served model, warmed for a mix's shapes."""

    def __init__(self, cell: harness.Cell, devices: List[Any]):
        from kubeflow_tpu.models.mimo import MimoConfig
        from kubeflow_tpu.serving.server import GenerativeModel, ModelServer

        self.sizes = s = sizes_of(cell.config)
        self.deploy = d = cell.deploy
        self.mix = cell.mix
        self.mcfg = MimoConfig(**{**s, "layer_kinds": tuple(s["layer_kinds"]),
                                  "moe_layers": tuple(s["moe_layers"])})
        self.new_tokens = int(d["max_new_tokens"])
        self.model = GenerativeModel(
            name=MODEL, apply_fn=None, params=weights_mimo.program_tree(cell.seed, s),
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

    def drop_weights(self) -> None:
        """Frees the weights' 6.9 GB while the engine is idle (limit
        readings: the reference needs the room between two windows)."""
        self.model.params = self.engine.params = None
        gc.collect()

    def load_weights(self, seed: int) -> None:
        """Another seed's weights into the live engine (limit readings):
        the programs take the parameters as an argument."""
        self.drop_weights()
        self.model.params = self.engine.params = weights_mimo.program_tree(seed, self.sizes)

    def warm(self) -> None:
        """The one chunk shape at every view width, and the decode program
        at every view width: one dummy prompt of the mix's longest length
        passes through all of them (a chunk's program is keyed by its
        shape and its view, not by the prompt's chunk count)."""
        spec = self.mix["prompt_len"]
        hi = int(spec.get("max", spec.get("value", 1)))
        self.engine.prewarm(hi, group_sizes=[1])

    def close(self) -> None:
        self.httpd.close()
        self.server.close()
        self.model.close()
        self.model = self.engine = self.server = None
        gc.collect()


class MimoDrive(Drive):
    """``Drive`` plus a sampler of the program's own counters: blocks in
    use by kind, and the assignments that landed on held experts."""

    def run(self) -> float:
        from kubeflow_tpu.runtime.metrics import METRICS

        samples: List[Any] = []
        stop = threading.Event()
        eid = self.server.engine.engine_id

        def sample() -> None:
            while not stop.wait(0.05):
                samples.append((
                    time.perf_counter(),
                    METRICS.value("serving_moe_assignments_total", held="true"),
                    METRICS.value("serving_kv_blocks_used", replica=eid, kind="full"),
                    METRICS.value("serving_kv_blocks_used", replica=eid, kind="window")))

        thread = threading.Thread(target=sample, daemon=True)
        thread.start()
        try:
            t_open = super().run()
        finally:
            stop.set()
            thread.join()
        self.samples = np.asarray(samples, float).reshape(-1, 4)
        return t_open

    def observations(self) -> Dict[str, Any]:
        obs = super().observations()
        t = self.samples[:, 0] - self.t_open
        if len(t):
            held = np.interp([0.0, self.seconds], t, self.samples[:, 1])
            obs["moe_assignments_held"] = float(held[1] - held[0])
        # the traced part of the window (Drive._trace): a third of the way in
        lo = self.seconds / 3
        during = (t >= lo) & (t <= lo + float(self.server.mix.get("trace_s", 3.0)))
        obs["kv_blocks_used_full"] = self.samples[during, 2].tolist()
        obs["kv_blocks_used_window"] = self.samples[during, 3].tolist()
        obs["prefill_program_name"] = "prefill_chunk"
        return obs


# -- correct ---------------------------------------------------------------------

#: a sequence is padded to the next of these for the reference: one
#: compilation a length, layer kind and variant, so few of them (the two
#: short ones are the toy cells'); each a multiple of its query block
LENGTHS = (64, 128, 1024, 2048, 4096, 8192)


def reference_gaps(drive: Drive, picks: List[int], seed: int, sizes: Dict[str, Any],
                   variants=()):
    """The plain reference over each sampled prompt with its served tokens,
    a layer at a time (each layer's float32 weights made, used for every
    sampled sequence, and dropped). Returns (gaps of the served tokens
    [len(picks), new], {name: gaps of the tokens a VARIANT of the reference
    puts first at the same positions}); a variant is (name, cast, fault):
    the reference at a lower precision, or computing a wrong model."""
    import jax
    import jax.numpy as jnp

    fs = ref.frozen(sizes)
    new = drive.server.new_tokens
    seqs = [np.asarray(drive.replies[i], np.int32) for i in picks]
    top = weights_mimo.top_canonical(seed, sizes)
    xs = [top["embedding"][jnp.asarray(np.pad(
        q, (0, next(n for n in LENGTHS if n >= len(q)) - len(q))))] for q in seqs]
    ys = {name: list(xs) for name, _, _ in variants}
    for i, (kind, moe) in enumerate(zip(sizes["layer_kinds"], sizes["moe_layers"])):
        w = weights_mimo.layer_canonical(seed, sizes, i)
        kw = dict(window=bool(kind), moe=bool(moe))
        xs = [ref.block_jit(fs, w, x, **kw) for x in xs]
        for name, cast, fault in variants:
            ys[name] = [ref.block_jit(fs, w, y, cast=cast, fault=fault, **kw)
                        for y in ys[name]]
        del w
    head = jax.jit(lambda t, h, c: ref.logits_at(sizes, t, h, c), static_argnums=2)
    served, other = [], {name: [] for name, _, _ in variants}
    for r, i in enumerate(picks):
        n = len(drive.arrivals[i].prompt)
        at = n - 1 + np.arange(new)
        logits = head(top, xs[r][at], None)
        served.append(np.asarray(ref.gaps_under_best(logits, jnp.asarray(seqs[r][n:n + new]))))
        for name, cast, _ in variants:
            best = jnp.argmax(head(top, ys[name][r][at], cast), axis=-1)
            other[name].append(np.asarray(ref.gaps_under_best(logits, best)))
    return np.stack(served), {name: np.stack(g) for name, g in other.items()}


def run(cell: harness.Cell, devices: List[Any], t0: float) -> harness.Outcome:
    server = Server(cell, devices)
    sizes = server.sizes
    arrivals = traffic.arrivals(cell.mix, sizes["vocab_size"], cell.seed, cell.seconds)
    profiler = harness.Profiler(cell) if cell.trace else None
    drive = MimoDrive(server, arrivals, cell.seconds, profiler)
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
    each fault's (the tokens a reference that ignores the window, leaves
    the sink out, or chooses seven experts puts first)."""
    server = Server(cell, devices)
    sizes, vocab = server.sizes, server.sizes["vocab_size"]
    count = int(cell.params.get("check_requests", 16))
    for n, seed in enumerate(seeds):
        if n:
            server.load_weights(seed)
        drive = MimoDrive(server, traffic.arrivals(cell.mix, vocab, seed, cell.seconds),
                          cell.seconds)
        drive.run()
        picks = sample_requests(drive, seed, count)
        server.drop_weights()
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
