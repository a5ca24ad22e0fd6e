"""Runner ``nemotron_serve``: the Nemotron configuration behind the same
``ModelServer`` + ``GenerativeModel`` + ``ContinuousBatcher`` as
``sarvam_serve``, driven by the same open-loop ``Drive`` over loopback HTTP.

What differs is the model family (``NemotronConfig``: Mamba-2 state-space
layers whose fixed-size state a slot is carried across the prompt's prefill
chunks, latent expert layers of relu² experts, one GQA attention layer over
a paged arena), the weights (``weights_nemotron``: a layer at a time) and
the reference the served tokens are held to (``reference/nemotron.py``: the
recurrence one position at a time over whole sequences, one layer at a
time). ``correct`` compares as the Sarvam cell does: after the window,
``check_requests`` finished requests drawn from the seed, the longest among
them, go through the reference with their served tokens, and what is
compared is ``served_off_best_pct``, the percent of the checked served
tokens whose reference logit lies under the reference's best at their
position; the widest gap (``served_logit_gap_sd``) is reported beside it.
The traffic's prompts end hundreds of positions past their last chunk
boundary, where what a state carried over it has mostly decayed, so a
second number holds the carry itself: after the window a CARRY PROBE of
prompts that end 1-3 positions past a chunk boundary goes to the engine,
and ``carry_off_best_pct`` is the same share over their first served
tokens.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from .. import harness, traffic, weights_nemotron
from ..reference import nemotron as ref
from .gpt_serve import MODEL, Drive, malformed, sample_requests
from .sarvam_serve import off_best_pct, summary

#: the readings a limit's upper end is set from: (name, cast, fault, over
#: how many of the shortest checked requests; 0: every one)
VARIANTS = (("control_fp8", ref.fp8_cast, None, 0),
            ("fault_state_reset", None, "state_reset", 3),
            ("fault_conv_not_carried", None, "conv_not_carried", 3),
            ("fault_no_D", None, "no_D", 3),
            ("fault_no_gate", None, "no_gate", 3),
            ("fault_no_routed_scaling", None, "no_routed_scaling", 3))

#: a sound reading beside the program's: the reference with its matmul
#: operands rounded to bfloat16, the configuration's own precision
SOUND = (("reference_bf16", ref.bf16_cast, None, 0),)


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys under the names the program, the reference and
    ``nemotron_cost`` use."""
    c = config
    return {
        "vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
        "pattern": c["hybrid_override_pattern"], "mamba_heads": c["mamba_num_heads"],
        "mamba_head_dim": c["mamba_head_dim"], "ssm_state": c["ssm_state_size"],
        "n_groups": c["n_groups"], "conv_kernel": c["conv_kernel"],
        "ssm_chunk": c["chunk_size"], "n_heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "n_experts": c["n_routed_experts_published"], "held_experts": c["n_routed_experts"],
        "experts_per_token": c["num_experts_per_tok"], "moe_latent": c["moe_latent_size"],
        "d_ff_expert": c["moe_intermediate_size"],
        "d_ff_shared": c["moe_shared_expert_intermediate_size"] * c["n_shared_experts"],
        "routed_scaling": float(c["routed_scaling_factor"]), "norm_eps": c["norm_eps"],
        "max_seq": c["max_position_embeddings"], "time_step_min": c["time_step_min"],
        "time_step_max": c["time_step_max"], "time_step_floor": c["time_step_floor"],
    }


#: the sizes the program's configuration takes (the rest are the weights')
_PROGRAM = ("vocab_size", "d_model", "pattern", "mamba_heads", "mamba_head_dim", "ssm_state",
            "n_groups", "conv_kernel", "ssm_chunk", "n_heads", "kv_heads", "head_dim",
            "n_experts", "held_experts", "experts_per_token", "moe_latent", "d_ff_expert",
            "d_ff_shared", "routed_scaling", "norm_eps", "max_seq")


class Server:
    """The served model, warmed for a mix's shapes."""

    def __init__(self, cell: harness.Cell, devices: List[Any]):
        from kubeflow_tpu.models.nemotron import NemotronConfig
        from kubeflow_tpu.serving.server import GenerativeModel, ModelServer

        self.sizes = s = sizes_of(cell.config)
        self.deploy = d = cell.deploy
        self.mix = cell.mix
        self.mcfg = NemotronConfig(**{k: s[k] for k in _PROGRAM})
        self.new_tokens = int(d["max_new_tokens"])
        self.prefill_chunk = int(d["prefill_chunk"])
        self.model = GenerativeModel(
            name=MODEL, apply_fn=None, params=weights_nemotron.program_tree(cell.seed, s),
            cfg=self.mcfg, max_new_tokens=self.new_tokens, slots=int(d["slots"]),
            kv_blocks=int(d["kv_blocks"]), kv_block_t=int(d["kv_block_t"]),
            prefill_chunk=self.prefill_chunk)
        self.server = ModelServer()
        self.server.add(self.model)
        self.httpd = self.server.serve(0)
        self.port = self.httpd.port
        self.engine = self.model._continuous_engine()
        harness.note("weights made, server up, engine built")
        self.warm()
        harness.note("every shape of the mix warmed")

    def drop_state(self) -> None:
        """Frees the weights and the caches while the engine is idle (limit
        readings: the reference needs the room between two windows; every
        slot is free then, and a prompt's first chunk zeroes its states)."""
        self.model.params = self.engine.params = self.engine.cache = None
        gc.collect()

    def load_weights(self, seed: int) -> None:
        """Another seed's weights into the live engine, and a fresh cache
        (limit readings): the programs take both as arguments."""
        self.drop_state()
        self.model.params = self.engine.params = weights_nemotron.program_tree(
            seed, self.sizes)
        self.engine.cache = self.engine.family.fresh_cache()

    def warm(self) -> None:
        """The one chunk shape at every view width, and the decode program
        at every view width: one dummy prompt of the mix's longest length."""
        spec = self.mix["prompt_len"]
        self.engine.prewarm(int(spec.get("max", spec.get("value", 1))), group_sizes=[1])

    def close(self) -> None:
        """Closes the server, which closes the engine and lets go of the
        weights and the caches: the reference needs the chip's memory."""
        import jax

        self.httpd.close()
        self.server.close()
        self.model = self.engine = self.server = self.httpd = None
        gc.collect()
        left = sum(a.nbytes for a in jax.live_arrays())
        harness.note(f"server closed: {left / 1e9:.2f} GB left on the device")


class NemotronDrive(Drive):
    """``Drive`` plus a sampler of the program's own counters: the
    assignments that landed on held experts and the Mamba states read and
    written."""

    def run(self) -> float:
        from kubeflow_tpu.runtime.metrics import METRICS

        samples: List[Any] = []
        stop = threading.Event()

        def sample() -> None:
            while not stop.wait(0.05):
                samples.append((time.perf_counter(),
                                METRICS.value("serving_moe_assignments_total", held="true"),
                                METRICS.value("serving_ssm_state_rows_total")))

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
            held, rows = (np.interp([0.0, self.seconds], t, self.samples[:, c]) for c in (1, 2))
            obs["moe_assignments_held"] = float(held[1] - held[0])
            obs["ssm_state_rows"] = float(rows[1] - rows[0])
        obs["prefill_program_name"] = "prefill_chunk"
        obs["prefill_chunk"] = int(self.server.prefill_chunk)
        return obs


# -- correct ---------------------------------------------------------------------

#: a sequence is padded to the next of these for the reference: one
#: compilation a length, layer kind and variant, so few of them (the short
#: ones are the toy cells'; 2,112 holds a carry probe at a chunk of 2,048);
#: each a multiple of the query block
LENGTHS = (64, 128, 256, 2112, 4096, 8192, 16384)

#: seconds a carry probe's requests may take on an idle engine
PROBE_TIMEOUT_S = 300.0


def checked(drive: Drive, picks: List[int]) -> Tuple[List[List[int]], List[int]]:
    """The picked requests' sequences (prompt and served tokens) and their
    prompts' lengths."""
    return [drive.replies[i] for i in picks], [len(drive.arrivals[i].prompt) for i in picks]


def carry_probe(server: Server, seed: int, requests: int,
                new_tokens: int) -> Tuple[List[List[int]], List[int]]:
    """``requests`` prompts of one prefill chunk and 1, 2 or 3 positions
    more, ids drawn from the seed, submitted to the engine together after
    the window, ``new_tokens`` each (the cell's ``carry_probe``). Every
    prompt's last chunk holds only those few positions, so the first
    served tokens are chosen by the Mamba states and the convolution
    windows carried over the chunk boundary (the traffic's prompts end
    hundreds of positions past theirs, where a state's memory has mostly
    turned over). Returns (prompt and served tokens, prompt lengths)."""
    rng = np.random.default_rng([int(seed), 13])
    chunk, vocab = server.prefill_chunk, server.sizes["vocab_size"]
    prompts = [rng.integers(1, vocab, chunk + 1 + i % 3).tolist() for i in range(requests)]
    futures = [server.engine.submit(p, new_tokens) for p in prompts]
    seqs = [p + list(f.result(timeout=PROBE_TIMEOUT_S)) for p, f in zip(prompts, futures)]
    return seqs, [len(p) for p in prompts]


def reference_logits(seqs: List[List[int]], prompts: List[int], chunk: int, seed: int,
                     sizes: Dict[str, Any], cast=None, fault=None) -> List[Any]:
    """The plain reference over each sequence (a prompt of ``prompts[r]``
    prefilled in chunks of ``chunk``, then its served tokens), a layer at a
    time (each layer's float32 weights made, used for every sequence, and
    dropped). Returns, per sequence, the logits at the positions that chose
    the served tokens, float32 [served, vocab]."""
    import jax
    import jax.numpy as jnp

    fs = ref.frozen(sizes)
    seqs = [np.asarray(q, np.int32) for q in seqs]
    padded = [next(n for n in LENGTHS if n >= len(q)) for q in seqs]
    knobs = [ref.knobs(sizes, n, p, chunk, fault) for n, p in zip(padded, prompts)]
    top = weights_nemotron.top_canonical(seed, sizes)
    xs = [top["embedding"][jnp.asarray(np.pad(q, (0, n - len(q))))]
          for q, n in zip(seqs, padded)]
    del top
    for i, kind in enumerate(sizes["pattern"]):
        w = weights_nemotron.layer_canonical(seed, sizes, i)
        for r, x in enumerate(xs):
            xs[r] = None
            xs[r] = ref.block_jit(fs, w, x, knobs[r], kind=kind, cast=cast)
            del x
        del w
    top = weights_nemotron.top_canonical(seed, sizes)
    head = jax.jit(lambda t, h, c: ref.logits_at(sizes, t, h, c), static_argnums=2)
    return [head(top, xs[r][p - 1 + np.arange(len(q) - p)], cast)
            for r, (q, p) in enumerate(zip(seqs, prompts))]


def reference_gaps(seqs: List[List[int]], prompts: List[int], chunk: int, seed: int,
                   sizes: Dict[str, Any], variants=()):
    """Returns (gaps of the served tokens [len(seqs), served], {name: gaps
    of the tokens a VARIANT of the reference puts first at the same
    positions}); a variant is (name, cast, fault, count), over the
    ``count`` shortest sequences (0: every one)."""
    import jax.numpy as jnp

    base = reference_logits(seqs, prompts, chunk, seed, sizes)
    served = np.stack([np.asarray(ref.gaps_under_best(lg, jnp.asarray(q[p:])))
                       for lg, q, p in zip(base, seqs, prompts)])
    order = sorted(range(len(seqs)), key=lambda r: len(seqs[r]))
    other = {}
    for name, cast, fault, count in variants:
        some = order[:count] if count else order
        wrong = reference_logits([seqs[r] for r in some], [prompts[r] for r in some], chunk,
                                 seed, sizes, cast, fault)
        other[name] = np.stack([np.asarray(ref.gaps_under_best(base[r], jnp.argmax(w, axis=-1)))
                                for r, w in zip(some, wrong)])
    return served, other


def run(cell: harness.Cell, devices: List[Any], t0: float) -> harness.Outcome:
    server = Server(cell, devices)
    sizes, chunk = server.sizes, server.prefill_chunk
    arrivals = traffic.arrivals(cell.mix, sizes["vocab_size"], cell.seed, cell.seconds)
    profiler = harness.Profiler(cell) if cell.trace else None
    drive = NemotronDrive(server, arrivals, cell.seconds, profiler)
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
    probe = carry_probe(server, cell.seed, **cell.params["carry_probe"])
    server.close()
    checks = [("malformed_replies", float(bad), float(cell.limits["malformed_replies"]))]
    read = {"served_off_best_pct": float("inf"), "served_logit_gap_sd": None}
    if picks:
        gaps, _ = reference_gaps(*checked(drive, picks), chunk, cell.seed, sizes)
        read = summary(gaps)
        harness.note(f"checked {read['tokens']} tokens: off the reference's best "
                     f"{read['off_first_token']} first, {read['off_later']} later, "
                     f"by request {read['off_by_request']}")
    checks.append(("served_off_best_pct", read["served_off_best_pct"],
                   float(cell.limits["served_off_best_pct"])))
    gaps, _ = reference_gaps(*probe, chunk, cell.seed, sizes)
    harness.note(f"carry probe: {int((~(gaps <= 0)).sum())} of {gaps.size} tokens off the "
                 f"reference's best")
    checks.append(("carry_off_best_pct", off_best_pct(gaps),
                   float(cell.limits["carry_off_best_pct"])))
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
    weights and a window at the cell's own load, then the carry probe;
    over the checked requests (``over`` "served") and over the probe
    (``over`` "carry") the share of tokens off the reference's best (the
    lower reading), for the first ``control_seeds`` seeds the control's,
    and for the first seed alone the sound bfloat16 reference's and each
    fault's (a reference pass over 8 requests of up to 16,384 positions is
    a minute on the chip; over the probe every variant reads every
    prompt)."""
    server = Server(cell, devices)
    sizes, vocab, chunk = server.sizes, server.sizes["vocab_size"], server.prefill_chunk
    count = int(cell.params.get("check_requests", 8))
    for n, seed in enumerate(seeds):
        if n:
            server.load_weights(seed)
        drive = NemotronDrive(server, traffic.arrivals(cell.mix, vocab, seed, cell.seconds),
                              cell.seconds)
        drive.run()
        picks = sample_requests(drive, seed, count)
        probe = carry_probe(server, seed, **cell.params["carry_probe"])
        server.drop_state()
        row = {"seed": seed, "requests": int(drive.ok.sum()), "checked": len(picks),
               "longest": max(len(drive.replies[i]) for i in picks)}
        variants = SOUND + VARIANTS if n == 0 else VARIANTS[:1] if n < control_seeds else ()
        served, wrong = reference_gaps(*checked(drive, picks), chunk, seed, sizes, variants)
        for name, gaps in wrong.items():
            yield {**row, "who": name, "over": "served", **summary(gaps)}
        yield {**row, "who": "program", "over": "served", **summary(served),
               "malformed_replies": malformed(drive, vocab)}
        every = tuple((name, cast, fault, 0) for name, cast, fault, _ in variants)
        served, wrong = reference_gaps(*probe, chunk, seed, sizes, every)
        for name, gaps in wrong.items():
            yield {**row, "who": name, "over": "carry", **summary(gaps)}
        yield {**row, "who": "program", "over": "carry", **summary(served)}
    server.close()
