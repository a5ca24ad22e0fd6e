"""Runner ``sdar_serve``: the SDAR configuration behind the same
``ModelServer`` + ``GenerativeModel`` + ``ContinuousBatcher`` as the other
serve runners, driven by the same open-loop ``Drive`` over loopback HTTP.

What differs is the model family (``SdarConfig``: generation by diffusion
over blocks, every expert held), the weights (``weights_sdar``: a tensor at
a time), what a request asks for (``"reveal_passes": true``: beside each
token the forward pass of its block that revealed it) and the reference the
served tokens are held to (``reference/sdar.py``, one sequence and one layer
at a time). ``correct``: after the window, 16 finished requests drawn from
the seed, the longest among them; the served ids and their reveal passes
give back EVERY denoising pass's input, which the reference runs under the
block mask. Two numbers, each 0 for a match: ``served_logit_gap_sd`` is the
widest gap by which a revealed token's reference logit lies under the
reference's best at its position and pass, in standard deviations of that
position's logits; ``reveal_choice_gap_sd`` is the same for the CHOICE of
position: how far the reference's confidence (log of the softmax's share of
its best id) at the position the program revealed lies under its largest
among the positions that pass saw masked, in the same unit.
"""

from __future__ import annotations

import gc
import http.client
import json
import time
from typing import Any, Dict, List, Optional

import numpy as np

from .. import harness, traffic, weights_sdar
from ..reference import sdar as ref
from .gpt_serve import GRACE_S, MODEL, Drive, sample_requests

#: the readings a limit's upper end is set from: (name, cast, fault)
VARIANTS = (("control_fp8", ref.fp8_cast, None),
            ("fault_causal_in_block", None, "causal_in_block"),
            ("fault_no_commit", None, "no_commit"), ("fault_top7", None, "top7"),
            ("fault_no_qk_norm", None, "no_qk_norm"),
            ("fault_left_to_right", None, "left_to_right"))
#: a sequence is padded for the reference to the next multiple of this many
#: positions (whole blocks of mask ids that nothing sees): one compilation a
#: length and variant, so few of them
PAD_TO = 512


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys under the names the program, the reference and
    ``sdar_cost`` use; the generation's settings (``generation``, each
    named under ``assumed``); the positions a slot may hold (the
    deployment's, not the model's 32,768)."""
    c, g = config, config["generation"]
    return {
        "vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"], "kv_heads": c["num_key_value_heads"],
        "head_dim": c["head_dim"], "n_layers": c["num_hidden_layers"],
        "d_ff_expert": c["moe_intermediate_size"], "n_experts": c["num_experts"],
        "held_experts": c["num_experts"], "experts_per_token": c["num_experts_per_tok"],
        "rope_theta": float(c["rope_theta"]), "norm_eps": c["rms_norm_eps"],
        "max_seq": int(c["runners"]["sdar_serve"]["max_seq"]),
        "block_len": g["block_length"], "denoise_steps": g["denoising_steps"],
        "confidence_threshold": g["confidence_threshold"], "mask_id": g["mask_token_id"],
    }


def without_mask(arrivals: List[traffic.Arrival], mask_id: int) -> List[traffic.Arrival]:
    """The generator's ids, drawn in [1, vocab - 1), onto [1, vocab) WITHOUT
    the mask id: a prompt never holds it."""
    return [traffic.Arrival(a.due_s, [t + (t >= mask_id) for t in a.prompt]) for a in arrivals]


def arrivals_of(mix, sizes: Dict[str, Any], seed: int, seconds: float):
    return without_mask(traffic.arrivals(mix, sizes["vocab_size"] - 1, seed, seconds),
                        sizes["mask_id"])


class Server:
    """The served model, warmed for a mix's shapes."""

    def __init__(self, cell: harness.Cell, devices: List[Any]):
        from kubeflow_tpu.models.sdar import SdarConfig
        from kubeflow_tpu.serving.server import GenerativeModel, ModelServer

        self.sizes = s = sizes_of(cell.config)
        self.deploy = d = cell.deploy
        self.mix = cell.mix
        self.mcfg = SdarConfig(**s)
        self.new_tokens = int(d["max_new_tokens"])
        self.model = GenerativeModel(
            name=MODEL, apply_fn=None, params=weights_sdar.program_tree(cell.seed, s),
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
        """Frees the weights and the arenas while the engine is idle (limit
        readings: the reference needs the room between two windows; every
        slot is free then, and an activation sets all of a slot's state)."""
        self.model.params = self.engine.params = self.engine.cache = None
        gc.collect()

    def load_weights(self, seed: int) -> None:
        """Another seed's weights into the live engine, and a fresh cache
        (limit readings): the programs take both as arguments."""
        self.drop_state()
        self.model.params = self.engine.params = weights_sdar.program_tree(seed, self.sizes)
        self.engine.cache = self.engine.family.fresh_cache()

    def warm(self) -> None:
        """The chunk program at every view width the mix's prompts reach
        (one chunk holds a whole prompt, so a prompt a width), and the
        decode program at every view width (the engine's first prewarm)."""
        spec = self.mix["prompt_len"]
        hi = int(spec.get("max", spec.get("value", 1)))
        kv = self.engine.kv
        for width in kv.view_widths:
            self.engine.prewarm(min(width * kv.block_t, hi), group_sizes=[1])
            if width * kv.block_t >= hi:
                break

    def close(self) -> None:
        self.httpd.close()
        self.server.close()
        self.model.close()
        self.model = self.engine = self.server = None
        gc.collect()


class SdarDrive(Drive):
    """``Drive`` whose requests ask for their reveal passes, plus what the
    cell's readers need to know of the engine."""

    def __init__(self, server, arrivals, seconds, profiler=None):
        super().__init__(server, arrivals, seconds, profiler)
        self.bodies = [json.dumps({"instances": [a.prompt], "reveal_passes": True}).encode()
                       for a in arrivals]
        self.marks: List[Optional[List[int]]] = [None] * len(arrivals)

    def _post(self, i: int) -> None:
        deadline = max(1.0, self.seconds - self.due[i]) + GRACE_S
        with harness.span("bench.send"):
            self.sent[i] = time.perf_counter() - self.t_open
            conn = http.client.HTTPConnection("127.0.0.1", self.server.port, timeout=deadline)
        try:
            with harness.span("bench.http_wait"):
                conn.request("POST", f"/v1/models/{MODEL}:predict", self.bodies[i],
                             {"content-type": "application/json"})
                resp = conn.getresponse()
                data = resp.read()
            self.done[i] = time.perf_counter() - self.t_open
            if resp.status == 200:
                reply = json.loads(data)
                self.replies[i] = reply["predictions"][0]
                self.marks[i] = reply["reveal_passes"][0]
                self.ok[i] = True
        except (OSError, http.client.HTTPException, ValueError, KeyError):
            self.done[i] = time.perf_counter() - self.t_open
        finally:
            conn.close()

    def observations(self) -> Dict[str, Any]:
        obs = super().observations()
        obs["prefill_program_name"] = "prefill_chunk"
        return obs


# -- correct ---------------------------------------------------------------------

def malformed(drive: SdarDrive, sizes: Dict[str, Any]) -> int:
    """Replies that do not echo their prompt, are not ``max_new_tokens``
    longer than it, hold an id outside the vocabulary, or whose reveal
    passes are not one a token in ``1 .. denoise_steps``; failed requests
    count too."""
    bad, new = 0, drive.server.new_tokens
    for a, reply, marks, ok in zip(drive.arrivals, drive.replies, drive.marks, drive.ok):
        n = len(a.prompt)
        if not ok or reply is None or len(reply) != n + new or reply[:n] != a.prompt \
                or not all(0 <= t < sizes["vocab_size"] for t in reply[n:]) \
                or marks is None or len(marks) != new \
                or not all(1 <= m <= sizes["denoise_steps"] for m in marks):
            bad += 1
    return bad


def reference_gaps(drive: SdarDrive, picks: List[int], seed: int, sizes: Dict[str, Any],
                   variants=()):
    """The plain reference over every denoising pass of each sampled
    request, a layer at a time (each layer's float32 weights made, used for
    every sampled sequence, and dropped) and a variant at a time. Returns
    ({"token": gaps of the revealed tokens, "choice": gaps of the revealed
    positions}, {name: the same of the tokens and positions a variant of the
    reference puts first at the same passes}), each a flat array over the
    sampled requests' passes. A variant is (name, cast, fault): the
    reference at a lower precision, or computing a wrong model."""
    import jax
    import jax.numpy as jnp

    fs = ref.frozen(sizes)
    B, mask_id = sizes["block_len"], sizes["mask_id"]
    cases = []
    for i in picks:
        n = len(drive.arrivals[i].prompt)
        seq = ref.passes_of(sizes, drive.replies[i][:n], drive.replies[i][n:], drive.marks[i])
        L = -(-len(seq["final"]) // PAD_TO) * PAD_TO
        final = np.pad(seq["final"], (0, L - len(seq["final"])), constant_values=mask_id)
        stale = np.pad(seq["stale"], (0, L - len(seq["stale"])), constant_values=-1)
        # every request the same count of variants: a pass a position of
        # every block that new_tokens can touch, the spare ones blocks of
        # mask ids at position 0 that nothing is held to
        V = (drive.server.new_tokens // B + 2) * sizes["denoise_steps"]
        spare = V - len(seq["ids"])
        cases.append({
            "final": jnp.asarray(final), "stale": jnp.asarray(stale),
            "ids": jnp.asarray(np.pad(seq["ids"], ((0, spare), (0, 0)),
                                      constant_values=mask_id)),
            "block": jnp.asarray(np.pad(seq["block"], (0, spare)), jnp.int32),
            "shown": np.pad(seq["shown"].astype(bool), ((0, spare), (0, 0))),
            "masked": np.pad(seq["masked"].astype(bool), ((0, spare), (0, 0))),
            "tokens": np.pad(np.where(seq["shown"], seq["final"][
                seq["block"][:, None] * B + np.arange(B)], 0), ((0, spare), (0, 0)))})
    top = weights_sdar.top_canonical(seed, sizes)

    def hidden(cast, fault):
        """The last layer's output at every variant's rows, a request."""
        xs = [top["embedding"][c["final"]] for c in cases]
        xvs = [top["embedding"][c["ids"]] for c in cases]
        for i in range(sizes["n_layers"]):
            w = weights_sdar.layer_canonical(seed, sizes, i)
            for r, c in enumerate(cases):
                xs[r], xvs[r] = ref.two_streams_jit(fs, w, xs[r], xvs[r], c["block"],
                                                    c["stale"], cast=cast, fault=fault)
            del w
            jax.block_until_ready(xvs)
            harness.note(f"reference layer {i}")
        harness.note(f"reference pass over {len(cases)} requests, "
                     f"{sum(int(x.shape[0]) for x in xs)} padded positions and "
                     f"{sum(int(x.shape[0]) for x in xvs)} passes "
                     f"({fault or ('float8' if cast else 'plain')})")
        return xvs

    head = jax.jit(lambda t, h, c: ref.logits_at(sizes, t, h, c), static_argnums=2)

    @jax.jit
    def read(logits, tokens, shown, masked, other_conf):
        """Of one request's passes [V, B, vocab]: the tokens' gaps at the
        shown positions, and the choice's gap a pass: the reference's
        confidence at the position ``other_conf`` (the program's, or a
        variant's) puts first against its largest among the masked."""
        token_gap = jnp.where(shown, ref.gaps_under_best(logits, tokens), 0.0)
        _, conf = ref.confidence(logits)
        sd = logits.std(-1).mean(-1)
        best = jnp.max(jnp.where(masked, conf, -jnp.inf), axis=-1)
        picked = jnp.min(jnp.where(other_conf, conf, jnp.inf), axis=-1)
        floor = jnp.log(sizes["confidence_threshold"])
        # more than the quota shown: each crossed the threshold, so the
        # reference's confidence there is held to the threshold
        many = jnp.sum(other_conf, -1) > sizes["block_len"] // sizes["denoise_steps"]
        choice_gap = jnp.maximum(jnp.where(many, floor, best) - picked, 0.0) / sd
        return token_gap, jnp.where(jnp.any(other_conf, -1), choice_gap, 0.0)

    def first_of(masked):
        return masked & (np.cumsum(masked, axis=-1) == 1)

    # hidden states are small (a few MB a request); a request's logits are
    # 0.6 GB a set, so they are made a request at a time and dropped
    sound = hidden(None, None)
    wrong = {name: None if fault == "left_to_right" else hidden(cast, fault)
             for name, cast, fault in variants}      # None: the sound model, a wrong choice
    served = {"token": [], "choice": []}
    other = {name: {"token": [], "choice": []} for name, _, _ in variants}
    for r, c in enumerate(cases):
        lg = head(top, sound[r], None)
        real = c["shown"].any(-1)
        t, ch = read(lg, jnp.asarray(c["tokens"]), c["shown"], c["masked"], c["shown"])
        served["token"].append(np.asarray(t)[c["shown"]])
        served["choice"].append(np.asarray(ch)[real])
        for name, cast, _ in variants:
            if wrong[name] is None:
                best, choice = jnp.asarray(c["tokens"]), first_of(c["masked"])
            else:
                best, conf = ref.confidence(head(top, wrong[name][r], cast))
                conf = np.where(c["masked"], np.asarray(conf), -np.inf)
                choice = c["masked"] & (conf == conf.max(-1, keepdims=True))
            t, ch = read(lg, best, c["shown"], c["masked"], choice)
            other[name]["token"].append(np.asarray(t)[c["shown"]])
            other[name]["choice"].append(np.asarray(ch)[real])
    join = lambda got: {k: np.concatenate(v) for k, v in got.items()}
    return join(served), {name: join(got) for name, got in other.items()}


def worst(gaps: np.ndarray) -> float:
    return float(np.where(np.isfinite(gaps), gaps, np.inf).max(initial=0.0))


def run(cell: harness.Cell, devices: List[Any], t0: float) -> harness.Outcome:
    server = Server(cell, devices)
    sizes = server.sizes
    arrivals = arrivals_of(cell.mix, sizes, cell.seed, cell.seconds)
    profiler = harness.Profiler(cell) if cell.trace else None
    drive = SdarDrive(server, arrivals, cell.seconds, profiler)
    t_open = drive.run()
    setup_s = t_open - t0
    peak = harness.allocator_peak(devices)
    obs = drive.observations()
    obs.update(sizes=sizes, chips=cell.chips, device_kind=devices[0].device_kind)
    bad = malformed(drive, sizes)
    picks = sample_requests(drive, cell.seed, int(cell.params.get("check_requests", 16)))
    server.close()
    checks = [("malformed_replies", float(bad), float(cell.limits["malformed_replies"]))]
    if picks:
        gaps, _ = reference_gaps(drive, picks, cell.seed, sizes)
        token, choice = worst(gaps["token"]), worst(gaps["choice"])
    else:
        token = choice = float("inf")
    checks.append(("served_logit_gap_sd", token, float(cell.limits["served_logit_gap_sd"])))
    checks.append(("reveal_choice_gap_sd", choice, float(cell.limits["reveal_choice_gap_sd"])))
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
    and positions' widest gaps (the lower readings), and for the first seeds
    the control's and each fault's (the tokens and the positions the
    reference at fp8, or a reference computing a wrong model, puts first at
    the same passes)."""
    server = Server(cell, devices)
    sizes = server.sizes
    count = int(cell.params.get("check_requests", 16))
    for n, seed in enumerate(seeds):
        if n:
            server.load_weights(seed)
        drive = SdarDrive(server, arrivals_of(cell.mix, sizes, seed, cell.seconds), cell.seconds)
        drive.run()
        picks = sample_requests(drive, seed, count)
        server.drop_state()
        row = {"seed": seed, "requests": int(drive.ok.sum()), "checked": len(picks),
               "longest": max(len(drive.replies[i]) for i in picks)}
        served, wrong = reference_gaps(drive, picks, seed, sizes,
                                       VARIANTS if n < control_seeds else ())
        for name, gaps in wrong.items():
            yield {**row, "who": name, "served_logit_gap_sd": worst(gaps["token"]),
                   "reveal_choice_gap_sd": worst(gaps["choice"]),
                   "tokens_off_the_reference_best": int((gaps["token"] > 0).sum()),
                   "choices_off_the_reference_best": int((gaps["choice"] > 0).sum())}
        yield {**row, "who": "program", "served_logit_gap_sd": worst(served["token"]),
               "reveal_choice_gap_sd": worst(served["choice"]),
               "malformed_replies": malformed(drive, sizes),
               "passes_checked": int(len(served["choice"])),
               "tokens_off_the_reference_best": int((served["token"] > 0).sum()),
               "choices_off_the_reference_best": int((served["choice"] > 0).sum())}
    server.close()
