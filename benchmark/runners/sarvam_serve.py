"""Runner ``sarvam_serve``: the Sarvam configuration behind the same
``ModelServer`` + ``GenerativeModel`` + ``ContinuousBatcher`` as
``mimo_serve``, driven by the same open-loop ``Drive`` over loopback HTTP.

What differs is the model family (``SarvamConfig``: latent attention over
one paged latent arena, prefilled expanded and decoded absorbed; a shared
expert beside a held share of the routed ones), the weights
(``weights_sarvam``: a layer at a time) and the reference the served tokens
are held to (``reference/sarvam.py``: the expanded form over whole
sequences, one layer at a time). ``correct`` compares as the MiMo cell
does: after the window, ``check_requests`` finished requests drawn from
the seed, the longest among them, go through the reference with their
served tokens. What is compared is ``served_off_best_pct``: the percent of
the checked served tokens whose reference logit lies under the reference's
best at their position, over every checked position. The widest such gap
(``served_logit_gap_sd``, in standard deviations of that position's
logits) is reported beside it and not compared: over 65,536 ids a
bfloat16 program's worst near-tie reads half of what the float8 control
does, where the share tells the two apart by seven times (PERF.md
section 2).
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Any, Dict, List

import numpy as np

from .. import harness, traffic, weights_sarvam
from ..reference import sarvam as ref
from .gpt_serve import MODEL, Drive, malformed, sample_requests

#: the readings a limit's upper end is set from: (name, cast, fault, over
#: how many of the shortest checked requests; 0: every one). The control
#: reads every checked request; a fault the shortest 3 (each variant is a
#: pass of the reference of its own, a minute over 8 requests on the chip)
VARIANTS = (("control_fp8", ref.fp8_cast, None, 0),
            ("fault_no_mscale", None, "no_mscale", 3),
            ("fault_no_yarn", None, "no_yarn", 3),
            ("fault_no_latent_norm", None, "no_latent_norm", 3),
            ("fault_no_routed_scaling", None, "no_routed_scaling", 3),
            ("fault_no_shared", None, "no_shared", 3))

#: a sound reading beside the program's: the reference with its matmul
#: operands rounded to bfloat16, the configuration's own precision (what
#: the rounding alone puts off the float32 reference's best)
SOUND = (("reference_bf16", ref.bf16_cast, None, 0),)


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys under the names the program, the reference and
    ``sarvam_cost`` use."""
    c, y = config, config["rope_scaling"]
    dense = int(c["first_k_dense_replace"])
    return {
        "vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"], "qk_nope_dim": c["qk_nope_head_dim"],
        "qk_rope_dim": c["qk_rope_head_dim"], "kv_lora_rank": c["kv_lora_rank"],
        "v_dim": c["v_head_dim"],
        "moe_layers": [0] * dense + [1] * (c["num_hidden_layers"] - dense),
        "d_ff_dense": c["intermediate_size"], "d_ff_expert": c["moe_intermediate_size"],
        "d_ff_shared": c["moe_intermediate_size"] * c["num_shared_experts"],
        "n_experts": c["num_experts_published"], "held_experts": c["num_experts"],
        "experts_per_token": c["num_experts_per_tok"],
        "routed_scaling": float(c["routed_scaling_factor"]),
        "rope_theta": float(c["rope_theta"]), "rope_factor": float(y["factor"]),
        "rope_original_max": int(y["original_max_position_embeddings"]),
        "beta_fast": float(y["beta_fast"]), "beta_slow": float(y["beta_slow"]),
        "mscale": float(y["mscale"]), "mscale_all_dim": float(y["mscale_all_dim"]),
        "norm_eps": c["rms_norm_eps"], "max_seq": c["max_position_embeddings"],
    }


class Server:
    """The served model, warmed for a mix's shapes."""

    def __init__(self, cell: harness.Cell, devices: List[Any]):
        from kubeflow_tpu.models.sarvam import SarvamConfig
        from kubeflow_tpu.serving.server import GenerativeModel, ModelServer

        self.sizes = s = sizes_of(cell.config)
        self.deploy = d = cell.deploy
        self.mix = cell.mix
        self.mcfg = SarvamConfig(**{**s, "moe_layers": tuple(s["moe_layers"])})
        self.new_tokens = int(d["max_new_tokens"])
        self.model = GenerativeModel(
            name=MODEL, apply_fn=None, params=weights_sarvam.program_tree(cell.seed, s),
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
        """Frees the weights (9.07 GB) and the arenas (3.36 GB) while the
        engine is idle (limit readings: the reference needs the room between
        two windows; every slot is free then, and an activation sets a
        slot's cursor anew)."""
        self.model.params = self.engine.params = self.engine.cache = None
        gc.collect()

    def load_weights(self, seed: int) -> None:
        """Another seed's weights into the live engine, and a fresh cache
        (limit readings): the programs take both as arguments."""
        self.drop_state()
        self.model.params = self.engine.params = weights_sarvam.program_tree(seed, self.sizes)
        self.engine.cache = self.engine.family.fresh_cache()

    def warm(self) -> None:
        """The one chunk shape at every view width, and the decode program
        at every view width: one dummy prompt of the mix's longest length
        passes through all of them (a chunk's program is keyed by its shape
        and its view, not by the prompt's chunk count)."""
        spec = self.mix["prompt_len"]
        hi = int(spec.get("max", spec.get("value", 1)))
        self.engine.prewarm(hi, group_sizes=[1])

    def close(self) -> None:
        """Closes the server, which closes the engine and lets go of the
        weights and the arenas (12.4 GB): the reference needs the chip's
        memory. What is still on the device afterwards goes to the log."""
        import jax

        self.httpd.close()
        self.server.close()
        self.model = self.engine = self.server = self.httpd = None
        gc.collect()
        left = sum(a.nbytes for a in jax.live_arrays())
        harness.note(f"server closed: {left / 1e9:.2f} GB left on the device")


class SarvamDrive(Drive):
    """``Drive`` plus a sampler of the program's own counters: the blocks in
    use, the assignments that landed on held experts, the latent pages the
    decode attention fetched."""

    def run(self) -> float:
        from kubeflow_tpu.runtime.metrics import METRICS

        samples: List[Any] = []
        stop = threading.Event()

        def sample() -> None:
            while not stop.wait(0.05):
                samples.append((
                    time.perf_counter(),
                    METRICS.value("serving_moe_assignments_total", held="true"),
                    METRICS.total("serving_kv_blocks_used")))

        thread = threading.Thread(target=sample, daemon=True)
        thread.start()
        try:
            t_open = super().run()
        finally:
            stop.set()
            thread.join()
        self.samples = np.asarray(samples, float).reshape(-1, 3)
        return t_open

    def observations(self) -> Dict[str, Any]:
        obs = super().observations()
        t = self.samples[:, 0] - self.t_open
        if len(t):
            held = np.interp([0.0, self.seconds], t, self.samples[:, 1])
            obs["moe_assignments_held"] = float(held[1] - held[0])
        obs["prefill_program_name"] = "prefill_chunk"
        obs["prefill_chunk"] = int(self.server.engine.prefill_chunk)
        return obs


# -- correct ---------------------------------------------------------------------

#: a sequence is padded to the next of these for the reference: one
#: compilation a length, layer kind and variant, so few of them (the short
#: ones are the toy cells'); each a multiple of the query block
LENGTHS = (64, 128, 256, 16384, 32768)


def reference_logits(drive: Drive, picks: List[int], seed: int, sizes: Dict[str, Any],
                     cast=None, fault=None) -> List[Any]:
    """The plain reference over each sampled prompt with its served tokens,
    a layer at a time (each layer's float32 weights made, used for every
    sampled sequence, and dropped). Returns, per pick, the logits at the
    positions that chose the served tokens, float32 [new, vocab]."""
    import jax
    import jax.numpy as jnp

    fs = ref.frozen(sizes)
    new = drive.server.new_tokens
    seqs = [np.asarray(drive.replies[i], np.int32) for i in picks]
    top = weights_sarvam.top_canonical(seed, sizes)
    xs = [top["embedding"][jnp.asarray(np.pad(
        q, (0, next(n for n in LENGTHS if n >= len(q)) - len(q))))] for q in seqs]
    # the float32 embedding and head (2.1 GB) are made again for the head:
    # a layer's weights, every sequence's stream and a 32,768-position
    # layer's temporaries take the rest of the chip
    del top
    knobs = ref.knobs(sizes, fault)
    for i, moe in enumerate(sizes["moe_layers"]):
        w = weights_sarvam.layer_canonical(seed, sizes, i)
        for r, x in enumerate(xs):
            xs[r] = None
            xs[r] = ref.block_jit(fs, w, x, knobs, moe=bool(moe), cast=cast)
            del x
        del w
    top = weights_sarvam.top_canonical(seed, sizes)
    head = jax.jit(lambda t, h, c: ref.logits_at(sizes, t, h, c), static_argnums=2)
    out = []
    for r, i in enumerate(picks):
        at = len(drive.arrivals[i].prompt) - 1 + np.arange(new)
        out.append(head(top, xs[r][at], cast))
    return out


def reference_gaps(drive: Drive, picks: List[int], seed: int, sizes: Dict[str, Any],
                   variants=()):
    """Returns (gaps of the served tokens [len(picks), new], {name: gaps of
    the tokens a VARIANT of the reference puts first at the same
    positions}); a variant is (name, cast, fault, picks): the reference at
    another precision, or computing a wrong model, over the ``picks``
    shortest of the checked requests (0: every one). One variant at a
    time: each keeps every sequence's residual stream."""
    import jax.numpy as jnp

    new = drive.server.new_tokens
    base = reference_logits(drive, picks, seed, sizes)
    served = np.stack([np.asarray(ref.gaps_under_best(
        lg, jnp.asarray(drive.replies[i][len(drive.arrivals[i].prompt):][:new])))
        for lg, i in zip(base, picks)])
    order = sorted(range(len(picks)), key=lambda r: len(drive.replies[picks[r]]))
    other = {}
    for name, cast, fault, count in variants:
        some = order[:count] if count else order
        wrong = reference_logits(drive, [picks[r] for r in some], seed, sizes, cast, fault)
        other[name] = np.stack([np.asarray(ref.gaps_under_best(base[r], jnp.argmax(w, axis=-1)))
                                for r, w in zip(some, wrong)])
    return served, other


def off_best_pct(gaps: np.ndarray) -> float:
    """Percent of the tokens whose reference logit lies under the
    reference's best at their position (a gap that is not a number counts
    as off)."""
    return 100.0 * float(np.mean(~(gaps <= 0)))


def summary(gaps: np.ndarray) -> Dict[str, Any]:
    """The numbers a row of readings gives: the share off the best, the
    widest gap, and where the tokens off lie (the answer's first token,
    which the prompt's last prefill chunk chose, against the rest)."""
    off = ~(gaps <= 0)
    return {"served_off_best_pct": off_best_pct(gaps),
            "served_logit_gap_sd": float(np.where(np.isfinite(gaps), gaps, np.inf).max()),
            "tokens": int(gaps.size), "off_first_token": int(off[:, 0].sum()),
            "off_later": int(off[:, 1:].sum()),
            "off_by_request": [int(n) for n in off.sum(axis=1)]}


def run(cell: harness.Cell, devices: List[Any], t0: float) -> harness.Outcome:
    server = Server(cell, devices)
    sizes = server.sizes
    arrivals = traffic.arrivals(cell.mix, sizes["vocab_size"], cell.seed, cell.seconds)
    profiler = harness.Profiler(cell) if cell.trace else None
    drive = SarvamDrive(server, arrivals, cell.seconds, profiler)
    t_open = drive.run()
    setup_s = t_open - t0
    peak = harness.allocator_peak(devices)
    obs = drive.observations()
    obs.update(sizes=sizes, chips=cell.chips, device_kind=devices[0].device_kind)
    bad = malformed(drive, sizes["vocab_size"])
    picks = sample_requests(drive, cell.seed, int(cell.params.get("check_requests", 8)))
    harness.note(f"window closed: {obs['requests_measured']} requests measured, "
                 f"{obs['requests_failed']} failed, {obs['requests_in_window']} done inside, "
                 f"allocator peak {peak / 1e9:.2f} GB")
    server.close()
    checks = [("malformed_replies", float(bad), float(cell.limits["malformed_replies"]))]
    read = {"served_off_best_pct": float("inf"), "served_logit_gap_sd": None}
    if picks:
        gaps, _ = reference_gaps(drive, picks, cell.seed, sizes)
        read = summary(gaps)
        harness.note(f"checked {read['tokens']} tokens: off the reference's best "
                     f"{read['off_first_token']} first, {read['off_later']} later, "
                     f"by request {read['off_by_request']}")
    checks.append(("served_off_best_pct", read["served_off_best_pct"],
                   float(cell.limits["served_off_best_pct"])))
    return harness.Outcome(
        obs=obs, attempted=obs["requests_measured"], failed=obs["requests_failed"],
        setup_s=setup_s, checks=checks, memory_peak_bytes=peak,
        trace_dir=profiler.dir if profiler else None,
        extra={"requests_in_window": obs["requests_in_window"],
               "checked_requests": len(picks),
               "served_logit_gap_sd": read["served_logit_gap_sd"]})


def limit_readings(cell: harness.Cell, devices: List[Any], seeds: List[int],
                   control_seeds: int):
    """For ``benchmark/limits.py``: one warmed server, per seed that seed's
    weights and a short window at the cell's own load; the served tokens'
    share off the reference's best (the lower reading), and for the first
    seeds the sound bfloat16 reference's, the control's (the tokens the
    reference at fp8 puts first, at the same positions) and each fault's."""
    server = Server(cell, devices)
    sizes, vocab = server.sizes, server.sizes["vocab_size"]
    count = int(cell.params.get("check_requests", 8))
    for n, seed in enumerate(seeds):
        if n:
            server.load_weights(seed)
        drive = SarvamDrive(server, traffic.arrivals(cell.mix, vocab, seed, cell.seconds),
                            cell.seconds)
        drive.run()
        picks = sample_requests(drive, seed, count)
        server.drop_state()
        row = {"seed": seed, "requests": int(drive.ok.sum()), "checked": len(picks),
               "longest": max(len(drive.replies[i]) for i in picks)}
        served, wrong = reference_gaps(drive, picks, seed, sizes,
                                       SOUND + VARIANTS if n < control_seeds else ())
        for name, gaps in wrong.items():
            yield {**row, "who": name, **summary(gaps)}
        yield {**row, "who": "program", **summary(served),
               "malformed_replies": malformed(drive, vocab)}
    server.close()
