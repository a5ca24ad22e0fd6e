"""Runner ``gpt_serve``: ``ModelServer`` + ``GenerativeModel`` over real
loopback HTTP, in this one process (one process holds the chip).

Open loop: one sender thread fires each request at its due time into a
pool of blocking HTTP clients, one prompt per request; the server answers
with the whole sequence (no streaming), ``max_new_tokens`` long. Latency
runs from when a request was DUE to its reply. The lead-in of the mix
fills the engine before the window opens and counts as set-up.
"""

from __future__ import annotations

import gc
import http.client
import json
import re
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional

import numpy as np

from .. import harness, traffic, weights
from ..reference import gpt as ref
from .gpt_train import sizes_of

MODEL = "gpt"
CLIENTS = 512                 # blocking HTTP clients; more due than this queue
GRACE_S = 60.0                # how long past the close a reply is waited for
HISTOGRAMS = ("serving_ttft_seconds", "serving_queue_wait_seconds",
              "serving_request_seconds")


def scrape(names=HISTOGRAMS) -> Dict[str, float]:
    """``<name>_sum`` and ``<name>_count`` of the program's histograms, read
    from its own exposition text (what ``/metrics`` serves)."""
    from kubeflow_tpu.runtime.metrics import METRICS

    out: Dict[str, float] = {}
    text = METRICS.render()
    for name in names:
        for part in ("sum", "count"):
            m = re.search(rf"^{name}_{part}(?:{{[^}}]*}})? ([0-9.eE+\-]+)", text, re.M)
            out[f"{name}_{part}"] = float(m.group(1)) if m else 0.0
    return out


class Server:
    """The served model, warmed for a mix's shapes."""

    def __init__(self, cell: harness.Cell, devices: List[Any]):
        from kubeflow_tpu.models.gpt import GptConfig
        from kubeflow_tpu.serving.server import GenerativeModel, ModelServer

        self.sizes = s = sizes_of(cell.config)
        self.deploy = d = cell.deploy
        self.mix = cell.mix
        self.gcfg = GptConfig(vocab_size=s["vocab_size"], d_model=s["n_embd"],
                              n_layers=s["n_layer"], n_heads=s["n_head"],
                              d_ff=s["n_inner"], max_seq=s["n_positions"])
        self.new_tokens = int(d["max_new_tokens"])
        self.model = GenerativeModel(
            name=MODEL, apply_fn=None, params=self.make_params(cell.seed),
            cfg=self.gcfg, max_new_tokens=self.new_tokens, slots=int(d["slots"]),
            kv_blocks=int(d["kv_blocks"]), kv_block_t=int(d["kv_block_t"]))
        self.server = ModelServer()
        self.server.add(self.model)
        self.httpd = self.server.serve(0)
        self.port = self.httpd.port
        self.engine = self.model._continuous_engine()
        harness.note("weights made, server up, engine built")
        self.warm()
        harness.note("every shape of the mix warmed")

    def make_params(self, seed: int):
        import jax

        canon = weights.gpt_canonical(seed, self.sizes)
        return jax.jit(lambda c: weights.gpt_tree(c, False))(canon)

    def load_weights(self, seed: int) -> None:
        """Another seed's weights into the live engine (limit readings):
        its programs take the parameters as an argument, so nothing
        recompiles."""
        self.model.params = self.engine.params = self.make_params(seed)

    def warm(self) -> None:
        """Every shape this mix will use and no other: the prefill buckets
        its prompt lengths fall in at every admission-group size (the
        engine traces its adopt per size), each count of prefill chunks for
        prompts above the chunk, and the decode chunk."""
        from kubeflow_tpu.serving.continuous import PREFILL_BUCKETS

        spec = self.mix["prompt_len"]
        lo = int(spec.get("min", spec.get("value", 1)))
        hi = int(spec.get("max", spec.get("value", 1)))
        chunk = self.engine.prefill_chunk or PREFILL_BUCKETS[-1]
        prev = 0
        for b in PREFILL_BUCKETS:
            if b <= chunk and prev < hi and b >= lo:
                self.engine.prewarm(min(b, hi))
                harness.note(f"prefill bucket {b}: admission groups of 1..{self.engine._group_pad}")
            prev = b
        for n in range(2, -(-hi // chunk) + 1):
            self.engine.prewarm(min(n * chunk, hi), group_sizes=[1])

    def close(self) -> None:
        self.httpd.close()
        self.server.close()
        self.model.close()
        self.model = self.engine = self.server = None
        gc.collect()


class Drive:
    """One open-loop pass of arrivals through the HTTP surface."""

    def __init__(self, server: Server, arrivals: List[traffic.Arrival], seconds: float,
                 profiler: Optional[harness.Profiler] = None):
        self.server, self.arrivals, self.seconds = server, arrivals, seconds
        self.profiler = profiler
        n = len(arrivals)
        self.bodies = [json.dumps({"instances": [a.prompt]}).encode() for a in arrivals]
        self.due = np.array([a.due_s for a in arrivals])
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.ok = np.zeros(n, bool)
        self.replies: List[Optional[List[int]]] = [None] * n
        self.snap_open: Dict[str, float] = {}
        self.snap_close: Dict[str, float] = {}
        self.kv_blocks_used: List[float] = []
        self.t_open = 0.0

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
                self.replies[i] = json.loads(data)["predictions"][0]
                self.ok[i] = True
        except (OSError, http.client.HTTPException, ValueError, KeyError):
            self.done[i] = time.perf_counter() - self.t_open
        finally:
            conn.close()

    def _trace(self) -> None:
        """A few seconds of the window under the profiler, a third of the
        way in, sampling the arena's blocks in use meanwhile."""
        from kubeflow_tpu.runtime.metrics import METRICS

        time.sleep(max(0.0, self.t_open + self.seconds / 3 - time.perf_counter()))
        self.profiler.start()
        end = time.perf_counter() + float(self.server.mix.get("trace_s", 3.0))
        while time.perf_counter() < end:
            self.kv_blocks_used.append(METRICS.total("serving_kv_blocks_used"))
            time.sleep(0.05)
        self.profiler.stop()

    def run(self) -> float:
        """Returns when the window opened (perf_counter)."""
        lead = -min(0.0, float(self.due.min()))
        self.t_open = time.perf_counter() + lead
        tracer = threading.Thread(target=self._trace) if self.profiler else None
        if tracer:
            tracer.start()
        opened = False
        with ThreadPoolExecutor(max_workers=CLIENTS, thread_name_prefix="client") as pool:
            futures = []
            for i in np.argsort(self.due, kind="stable"):
                if not opened and self.due[i] >= 0:
                    time.sleep(max(0.0, self.t_open - time.perf_counter()))
                    self.snap_open, opened = scrape(), True
                time.sleep(max(0.0, self.t_open + self.due[i] - time.perf_counter()))
                futures.append(pool.submit(self._post, int(i)))
            time.sleep(max(0.0, self.t_open + self.seconds - time.perf_counter()))
            self.snap_close = scrape()
            for f in futures:
                f.result()
        if tracer:
            tracer.join()
        return self.t_open

    # -- what the window says --------------------------------------------
    def observations(self) -> Dict[str, Any]:
        new = self.server.new_tokens
        prompt_len = np.array([len(a.prompt) for a in self.arrivals])
        n_out = np.array([len(r) - p if r is not None else 0
                          for r, p in zip(self.replies, prompt_len)])
        measured = self.due >= 0
        inside = self.ok & (self.done >= 0) & (self.done <= self.seconds)
        latency = np.where(np.isnan(self.done), self.seconds + GRACE_S, self.done) - self.due
        per_token = 1000.0 * latency / np.where(self.ok & (n_out > 0), n_out, new)
        delta = {k: self.snap_close[k] - self.snap_open.get(k, 0.0) for k in self.snap_close}
        return {
            "kind": "serve", "window_s": self.seconds,
            "requests_measured": int(measured.sum()),
            "requests_failed": int((measured & ~self.ok).sum()),
            "tokens_out_in_window": int(n_out[inside].sum()),
            "requests_in_window": int(inside.sum()),
            "prompt_len_in_window": prompt_len[inside].tolist(),
            "n_out_in_window": n_out[inside].tolist(),
            "ms_per_token": per_token[measured].tolist(),
            "late_ms": (1000.0 * (self.sent - self.due))[measured & ~np.isnan(self.sent)].tolist(),
            "client_latency_s_in_window": (self.done - self.sent)[inside].tolist(),
            "histograms": delta,
            "kv_blocks_used": self.kv_blocks_used,
            "kv_block_t": int(self.server.engine.kv_block_t),
            "decode_chunk": int(self.server.engine.chunk),
            "program_name": "step",
        }


# -- correct -----------------------------------------------------------------

def malformed(drive: Drive, vocab: int) -> int:
    """Replies that do not echo their prompt, are not ``max_new_tokens``
    longer than it, or hold an id outside the vocabulary; failed requests
    count too."""
    bad = 0
    for a, reply, ok in zip(drive.arrivals, drive.replies, drive.ok):
        n = len(a.prompt)
        if not ok or reply is None or len(reply) != n + drive.server.new_tokens \
                or reply[:n] != a.prompt or not all(0 <= t < vocab for t in reply[n:]):
            bad += 1
    return bad


def sample_requests(drive: Drive, seed: int, count: int) -> List[int]:
    """Indexes of finished requests, drawn from the seed, the longest in."""
    finished = [i for i, (ok, r) in enumerate(zip(drive.ok, drive.replies))
                if ok and r is not None and len(r) > len(drive.arrivals[i].prompt)]
    if not finished:
        return []
    longest = max(finished, key=lambda i: len(drive.replies[i]))
    rng = np.random.default_rng([int(seed), 9])
    rest = [i for i in finished if i != longest]
    picks = rng.permutation(len(rest))[:max(0, count - 1)]
    return [longest] + [rest[j] for j in picks]


def build_gap_fn(cast=None):
    """(canon, ids [b, L], positions [b, n], tokens [b, n]) -> gaps [b, n] of
    the served tokens under the reference's best, in sd of that position's
    reference logits; with ``cast`` also the gaps of the tokens the LOWER
    precision puts first (the control's reading)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def go(canon, ids, positions, tokens):
        h = jnp.take_along_axis(ref.hidden(canon, ids), positions[..., None], axis=1)
        logits = ref.logits_at(canon, h)
        out = ref.gaps_under_best(logits, tokens)
        if cast is None:
            return out, out
        low = jnp.take_along_axis(ref.hidden(canon, ids, cast), positions[..., None], axis=1)
        best = jnp.argmax(ref.logits_at(canon, low, cast), axis=-1)
        return out, ref.gaps_under_best(logits, best)

    return go


def served_gaps(drive: Drive, picks: List[int], seed: int, sizes: Dict[str, int],
                cast=None, rows_per_call: int = 4):
    """Runs the plain reference once over each sampled prompt with its
    served tokens, in blocks of rows. Returns (gaps of the served tokens,
    gaps of the control's tokens), each [len(picks), new]."""
    import jax.numpy as jnp

    canon = weights.gpt_canonical(seed, sizes)
    new, L = drive.server.new_tokens, sizes["n_positions"]
    go = build_gap_fn(cast)
    served, control = [], []
    for at in range(0, len(picks), rows_per_call):
        rows = picks[at:at + rows_per_call]
        pad = rows + [rows[-1]] * (rows_per_call - len(rows))
        ids = np.zeros((rows_per_call, L), np.int32)
        pos = np.zeros((rows_per_call, new), np.int32)
        tok = np.zeros((rows_per_call, new), np.int32)
        for r, i in enumerate(pad):
            seq, n = drive.replies[i], len(drive.arrivals[i].prompt)
            ids[r, :len(seq)] = seq
            pos[r] = n - 1 + np.arange(new)
            tok[r] = seq[n:n + new]
        a, b = go(canon, jnp.asarray(ids), jnp.asarray(pos), jnp.asarray(tok))
        served.append(np.asarray(a)[:len(rows)])
        control.append(np.asarray(b)[:len(rows)])
    return np.concatenate(served), np.concatenate(control)


def run(cell: harness.Cell, devices: List[Any], t0: float) -> harness.Outcome:
    server = Server(cell, devices)
    sizes = server.sizes
    arrivals = traffic.arrivals(cell.mix, sizes["vocab_size"], cell.seed, cell.seconds)
    profiler = harness.Profiler(cell) if cell.trace else None
    drive = Drive(server, arrivals, cell.seconds, profiler)
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
        gaps, _ = served_gaps(drive, picks, cell.seed, sizes)
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
    the altered-token fault's (one served token of each sampled request
    replaced by its neighbour in the vocabulary)."""
    server = Server(cell, devices)
    sizes, vocab = server.sizes, server.sizes["vocab_size"]
    count = int(cell.params.get("check_requests", 16))
    for n, seed in enumerate(seeds):
        if n:
            server.load_weights(seed)
        drive = Drive(server, traffic.arrivals(cell.mix, vocab, seed, cell.seconds),
                      cell.seconds)
        drive.run()
        picks = sample_requests(drive, seed, count)
        cast = ref.fp8_cast if n < control_seeds else None
        served, control = served_gaps(drive, picks, seed, sizes, cast)
        row = {"seed": seed, "requests": int(drive.ok.sum()), "checked": len(picks),
               "longest": max(len(drive.replies[i]) for i in picks)}
        yield {**row, "who": "program", "served_logit_gap_sd": float(served.max()),
               "malformed_replies": malformed(drive, vocab),
               "tokens_off_the_reference_best": int((served > 0).sum())}
        if cast is not None:
            yield {**row, "who": "control_fp8", "served_logit_gap_sd": float(control.max()),
                   "tokens_off_the_reference_best": int((control > 0).sum())}
            for i in picks:
                at = len(drive.arrivals[i].prompt) + 3
                drive.replies[i][at] = (drive.replies[i][at] + 1) % vocab
            altered, _ = served_gaps(drive, picks, seed, sizes)
            yield {**row, "who": "fault_token_altered",
                   "served_logit_gap_sd": float(altered.max())}
    server.close()
