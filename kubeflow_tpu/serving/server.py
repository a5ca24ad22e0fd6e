"""JAX model server: the TF-Serving-compatible predict surface.

API shape (what testing/test_tf_serving.py drives):
    POST /v1/models/<name>:predict   {"instances": [...]}
    ->                               {"predictions": [...]}
    GET  /v1/models/<name>           status/metadata

TPU-first serving decisions:
- ONE jitted forward per (model, padded batch-size bucket); requests are
  padded to the next bucket so XLA never sees a new shape (no recompiles
  in steady state, static shapes on the MXU),
- bf16 weights with f32 outputs, batch dimension sharded over the mesh
  batch axes when a mesh is configured.
"""

from __future__ import annotations

import math
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime.metrics import METRICS
from ..web.http import App, HttpError, Request
from .errors import DeadlineExceeded, FleetSaturated

BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)

#: per-request budget when the client sends neither the
#: ``X-Request-Deadline-Ms`` header nor a ``timeout_ms`` body field —
#: matches the old hard-coded ``result(timeout=600)`` ceiling
DEFAULT_DEADLINE_MS = 600_000.0

#: extra wait past the deadline for the engine to reap an expired slot
#: and hand back the partial tokens (reaping happens within ~one decode
#: chunk; the grace also covers event-pipeline fetch latency)
DEADLINE_GRACE_S = 5.0


def request_deadline_opts(req: Request, body: Any) -> Tuple[float, str]:
    """(absolute monotonic deadline, priority) for one predict request.

    The ``X-Request-Deadline-Ms`` header wins over the body's
    ``timeout_ms`` field; both express a RELATIVE budget in milliseconds
    from arrival. Zero/negative budgets are legal and expire immediately
    (an upstream that already blew its own deadline should get the 504
    without costing this server a slot). Priority comes from the body's
    ``priority`` field or the ``X-Request-Priority`` header."""
    raw: Any = req.header("x-request-deadline-ms") or None
    if raw is None and isinstance(body, dict):
        raw = body.get("timeout_ms")
    try:
        ms = float(raw) if raw is not None else DEFAULT_DEADLINE_MS
    except (TypeError, ValueError):
        raise HttpError(400, f"bad deadline {raw!r}: expected milliseconds") \
            from None
    priority = ""
    if isinstance(body, dict):
        priority = str(body.get("priority") or "")
    priority = priority or req.header("x-request-priority") or "interactive"
    if priority not in ("interactive", "batch"):
        raise HttpError(
            400, f"priority {priority!r}: expected 'interactive' or 'batch'")
    return time.monotonic() + ms / 1000.0, priority


def retry_after_headers(e: FleetSaturated) -> Dict[str, str]:
    """``Retry-After`` from the router's queue-drain hint (whole seconds,
    minimum 1 — the header's unit)."""
    hint = e.retry_after_s if e.retry_after_s else 1.0
    return {"Retry-After": str(max(1, int(math.ceil(hint))))}


@dataclass
class ServedModel:
    """One deployable model: a pure ``apply(params, batch) -> out`` pair."""

    name: str
    apply_fn: Callable[[Any, jax.Array], jax.Array]
    params: Any
    input_dtype: Any = jnp.float32
    version: str = "1"
    # Optional preprocessing: raw JSON instances -> np.ndarray batch.
    preprocess: Optional[Callable[[Sequence[Any]], np.ndarray]] = None
    _compiled: Dict[int, Callable] = field(default_factory=dict)
    _lock: threading.Lock = field(default_factory=threading.Lock)

    def _fn_for_bucket(self, bucket: int) -> Callable:
        with self._lock:
            if bucket not in self._compiled:
                self._compiled[bucket] = jax.jit(self.apply_fn)
            return self._compiled[bucket]

    def predict(self, instances: Sequence[Any]) -> List[Any]:
        if not instances:
            return []
        if self.preprocess is not None:
            batch = np.asarray(self.preprocess(instances))
        else:
            batch = np.asarray(instances, dtype=np.dtype(jnp.dtype(self.input_dtype).name))
        n = batch.shape[0]
        bucket = next((b for b in BATCH_BUCKETS if b >= n), None)
        if bucket is None:
            raise HttpError(413, f"batch of {n} exceeds max {BATCH_BUCKETS[-1]}")
        if bucket != n:
            pad = np.repeat(batch[:1], bucket - n, axis=0)
            batch = np.concatenate([batch, pad], axis=0)
        fn = self._fn_for_bucket(bucket)
        out = np.asarray(fn(self.params, jnp.asarray(batch)))
        return out[:n].tolist()


class ModelServer:
    """Hosts ServedModels over the predict API; servable with app.serve().

    ``batching=True`` coalesces concurrent requests per model into one
    padded forward (serving/batching.py) — the TPU-shaped default for
    production; off by default so single-request paths stay trivial."""

    def __init__(self, batching: bool = False, max_batch: int = BATCH_BUCKETS[-1],
                 max_wait_ms: float = 5.0):
        if max_batch > BATCH_BUCKETS[-1]:
            # A combined batch above the largest serving bucket would 413 on
            # every co-batched request.
            raise ValueError(f"max_batch {max_batch} exceeds largest bucket {BATCH_BUCKETS[-1]}")
        self.models: Dict[str, ServedModel] = {}
        self.app = App("model-server")
        self._batching = batching
        self._max_batch = max_batch
        self._max_wait_ms = max_wait_ms
        self._batchers: Dict[str, "DynamicBatcher"] = {}
        self._register_routes()
        # /metrics + /debug/traces + /debug/vars on the serving port itself:
        # the SLO histograms live in this process, so the scrape must too
        from ..runtime.obs import mount_observability

        mount_observability(self.app)

    def add(self, model: ServedModel) -> "ModelServer":
        self.models[model.name] = model
        if self._batching:
            from .batching import DynamicBatcher

            old = self._batchers.pop(model.name, None)
            if old is not None:
                old.close()  # model reload: stop the old worker, release params
            self._batchers[model.name] = DynamicBatcher(
                model.predict,
                max_batch=self._max_batch,
                max_wait_ms=self._max_wait_ms,
                name=model.name,
            )
        return self

    def _predict(self, model: ServedModel, instances,
                 deadline: Optional[float] = None,
                 priority: str = "interactive",
                 model_id: Optional[str] = None,
                 reveal_passes: Optional[List[Any]] = None) -> List[Any]:
        from .batching import BatcherClosed

        batcher = self._batchers.get(model.name)
        if batcher is not None:
            try:
                return batcher.predict(instances, deadline=deadline)
            except BatcherClosed:
                # Model hot-reload raced this request: the batcher we fetched
                # was closed by add(). Serve directly — correctness over
                # coalescing for the handful of in-flight requests.
                pass
        if isinstance(model, GenerativeModel):
            return model.predict(instances, deadline=deadline,
                                 priority=priority, model=model_id,
                                 reveal_passes=reveal_passes)
        return model.predict(instances)

    def close(self) -> None:
        """Stops the batchers and closes every served model that has an
        engine, then forgets the models: the routes the HTTP server keeps
        reach this object, and through it each model's weights."""
        for b in self._batchers.values():
            b.close()
        for model in self.models.values():
            if isinstance(model, GenerativeModel):
                model.close()
        self.models.clear()

    def _model(self, name: str) -> ServedModel:
        model = self.models.get(name)
        if model is None:
            raise HttpError(404, f"model {name!r} not loaded")
        return model

    def _register_routes(self) -> None:
        app = self.app

        @app.route("/healthz")
        def healthz(req: Request):
            return {"status": "ok", "models": sorted(self.models)}

        @app.route("/v1/models/<name>")
        def model_status(req: Request):
            model = self._model(req.params["name"])
            return {
                "model_version_status": [
                    {"version": model.version, "state": "AVAILABLE", "status": {"error_code": "OK"}}
                ]
            }

        @app.route("/v1/models/<name>:predict", methods=("POST",))
        def predict(req: Request):
            model = self._model(req.params["name"])
            body = req.json or {}
            instances = body.get("instances")
            if instances is None:
                raise HttpError(400, "body must carry 'instances'")
            deadline, priority = request_deadline_opts(req, body)
            # multiplexed servables route on the body's "model" id
            model_id = body.get("model") if isinstance(body, dict) else None
            # "reveal_passes": true asks, beside each generated token, for
            # the forward pass of its block that revealed it (a family that
            # generates by unmasking blocks; empty rows from any other)
            passes: Optional[List[Any]] = (
                [] if isinstance(body, dict) and body.get("reveal_passes") else None)

            t0 = time.perf_counter()
            try:
                predictions = self._predict(model, instances,
                                            deadline=deadline,
                                            priority=priority,
                                            model_id=model_id,
                                            reveal_passes=passes)
            except HttpError:
                raise
            except DeadlineExceeded as e:
                METRICS.counter("serving_predict_total", model=model.name, result="error").inc()
                raise HttpError(504, f"deadline exceeded: {e}") from None
            except Exception as e:
                METRICS.counter("serving_predict_total", model=model.name, result="error").inc()
                raise HttpError(400, f"inference failed: {e}") from None
            METRICS.counter("serving_predict_total", model=model.name, result="success").inc()
            METRICS.histogram("serving_predict_seconds", model=model.name).observe(
                time.perf_counter() - t0
            )
            if passes is None:
                return {"predictions": predictions}
            return {"predictions": predictions, "reveal_passes": passes}

    def serve(self, port: int = 0):
        return self.app.serve(port)


@dataclass
class GenerativeModel(ServedModel):
    """Serves autoregressive generation through the predict surface:
    instances = equal-length token-id prompts, predictions = full generated
    sequences. Decoding manages its own compilation cache (models/gpt.py
    generate), so the bucket-jit path is bypassed.

    ``continuous=True`` (the default since round 5) routes requests
    through the slot-based continuous-batching engine
    (serving/continuous.py): concurrent HTTP requests share one running
    decode batch, each sequence retiring at its own budget instead of the
    batch's max (VERDICT r3 #8). Sampling rides per-slot temperatures and
    keys inside the shared batch. Round 5's pipelined engine measures at
    0.9-1.1x the OFFLINE static oracle's tokens/s with consistently lower
    mean request latency on the mixed-budget bench
    (e2e/serving_bench.py:bench_continuous) — and online it needs no
    oracle grouping, so it is the right default. ``continuous=False``
    falls back to lockstep bucketed generate(); prompts longer than the
    engine's largest prefill bucket take that static path automatically,
    so the servable prompt range stays cfg.max_seq."""

    cfg: Any = None
    max_new_tokens: int = 16
    temperature: float = 0.0
    continuous: bool = True
    slots: int = 8
    #: >1 builds an EngineFleet (serving/fleet.py) instead of a single
    #: engine: prefix-aware routing + drain/handoff across N replicas
    replicas: int = 1
    #: autoscaler headroom; None pins the fleet at ``replicas``
    max_replicas: Optional[int] = None
    # -- ISSUE-12 engine knobs (docs/SERVING.md has the full table) --------
    #: paged (block-arena) KV layout; False keeps the contiguous parity path
    paged: bool = True
    #: allocatable arena blocks (None = contiguous-capacity parity)
    kv_blocks: Optional[int] = None
    #: requested arena tile (auto-shrunk to divide max_seq + the buckets)
    kv_block_t: int = 16
    #: chunked-prefill budget (None = largest prefill bucket; 0 disables —
    #: over-bucket prompts then fall back to the static generate() path)
    prefill_chunk: Optional[int] = None
    #: (draft_cfg, draft_params) enables speculative decoding
    spec_draft: Optional[Any] = None
    spec_k: int = 4
    # -- ISSUE-18 disaggregation knobs -------------------------------------
    #: KV arena storage precision: "bf16" (bit-parity ground truth) or
    #: "int8" (2x KV positions per HBM byte, tested logit tolerance)
    kv_dtype: str = "bf16"
    #: role pools for a disaggregated fleet, e.g. {"prefill": 1,
    #: "decode": 2}; None keeps homogeneous replicas
    pools: Optional[Dict[str, int]] = None
    #: model_id -> (cfg, params): multiplex several models over one fleet;
    #: requests pick one via the body's "model" field
    mux_models: Optional[Dict[str, Any]] = None
    #: model_id -> default admission class ("interactive"/"batch")
    model_slo: Optional[Dict[str, str]] = None

    def __post_init__(self):
        # Per-request sampling state: a base key seeded from OS entropy folded
        # with a monotone counter gives distinct draws per request without
        # re-seeding numpy/jax global state.
        self._rng_lock = threading.Lock()
        self._rng_counter = 0
        self._base_rng = jax.random.PRNGKey(
            int.from_bytes(os.urandom(4), "little")
        )
        self._engine = None
        self._engine_lock = threading.Lock()

    def _wants_fleet(self) -> bool:
        # pools and multiplexing are fleet-level concepts; a single engine
        # only exists for the plain one-replica case
        return bool(self.replicas > 1 or self.max_replicas or self.pools
                    or self.mux_models)

    def _continuous_engine(self):
        from .continuous import ContinuousBatcher

        engine_kwargs = dict(paged=self.paged, kv_blocks=self.kv_blocks,
                             kv_block_t=self.kv_block_t,
                             prefill_chunk=self.prefill_chunk,
                             spec_draft=self.spec_draft, spec_k=self.spec_k,
                             kv_dtype=self.kv_dtype)
        with self._engine_lock:
            if self._engine is None:
                if self._wants_fleet():
                    from .fleet import EngineFleet

                    self._engine = EngineFleet(
                        self.cfg, self.params, replicas=self.replicas,
                        max_replicas=self.max_replicas or max(self.replicas, 1),
                        slots=self.slots, name=self.name,
                        pools=self.pools, models=self.mux_models,
                        model_slo=self.model_slo,
                        engine_kwargs=engine_kwargs)
                else:
                    self._engine = ContinuousBatcher(self.cfg, self.params,
                                                     slots=self.slots,
                                                     **engine_kwargs)
            return self._engine

    def close(self) -> None:
        # Swap under the lock (close() racing _continuous_engine() must not
        # orphan a freshly-built engine), shut down outside it: engine close
        # joins worker threads and must not stall new-engine construction.
        with self._engine_lock:
            engine, self._engine = self._engine, None
        if engine is not None:
            engine.close()

    def predict(self, instances: Sequence[Any],
                deadline: Optional[float] = None,
                priority: str = "interactive",
                model: Optional[str] = None,
                reveal_passes: Optional[List[Any]] = None) -> List[Any]:
        """``reveal_passes``, where given, is filled with a row a prompt:
        the engine's mark beside each generated token (the forward pass of
        its block that revealed it, from a family that hands one out)."""
        from kubeflow_tpu.models.gpt import generate

        if not instances:
            return []
        if model and not self.mux_models:
            raise HttpError(400, f"servable {self.name!r} does not "
                                 "multiplex models")
        if self.mux_models and not model:
            raise HttpError(400, "body must carry 'model': this servable "
                                 f"multiplexes {sorted(self.mux_models)}")
        if deadline is None:
            # direct callers (tests, DynamicBatcher) get the server default
            deadline = time.monotonic() + DEFAULT_DEADLINE_MS / 1000.0
        prompts = np.asarray(instances, dtype=np.int32)
        if prompts.ndim != 2:
            raise HttpError(400, "instances must be equal-length token-id lists")
        from .continuous import PREFILL_BUCKETS

        # client errors must surface as 4xx BEFORE anything is enqueued or
        # compiled (a mid-listcomp failure would abandon submitted futures;
        # the static path's generate() would turn this into a 500)
        if prompts.shape[1] + self.max_new_tokens > self.cfg.max_seq:
            raise HttpError(413, "prompt + generation budget exceeds max_seq")
        # prompts longer than the engine's largest prefill bucket: chunked
        # prefill (ISSUE 12) serves them through the engine when enabled —
        # effective_prefill_chunk here MUST mirror the engine's own
        # resolution so routing and admission agree; when disabled they
        # take the static generate() path instead of erroring (flipping
        # continuous on must not shrink the servable prompt range below
        # cfg.max_seq — review finding, round 5)
        from .continuous import _block_tile, effective_prefill_chunk

        chunk = effective_prefill_chunk(
            self.prefill_chunk, self.cfg.max_seq,
            _block_tile(self.cfg.max_seq, self.kv_block_t)
            if self.paged else 1)
        if self.continuous and (prompts.shape[1] <= PREFILL_BUCKETS[-1]
                                or chunk > 0):
            from ..runtime.tracing import TRACER, format_traceparent

            eng = self._continuous_engine()
            # hand the engine our trace context: when this runs inside the
            # HTTP dispatch span, every serving.request span parents there
            # (continuing the client's traceparent if one came in)
            cur = TRACER.current_span()
            tp = format_traceparent(cur) if cur is not None else None
            futs: List[Any] = []
            # a multiplexed model's SLO class is deployment policy, not a
            # client choice: it overrides whatever the request asked for
            if model and self.model_slo and model in self.model_slo:
                priority = self.model_slo[model]
            submit_kw: Dict[str, Any] = (
                {"model": model or ""} if self._wants_fleet() else {})
            try:
                for row in prompts:
                    futs.append(eng.submit(row, self.max_new_tokens,
                                           temperature=self.temperature,
                                           traceparent=tp,
                                           deadline=deadline,
                                           priority=priority,
                                           **submit_kw))
                out = []
                for row, f in zip(prompts, futs):
                    # the wait derives from the request's own deadline: at
                    # expiry the engine reaps the slot and completes the
                    # future with the partial tokens (grace covers the reap)
                    remaining = max(0.0, deadline - time.monotonic())
                    out.append(row.tolist()
                               + f.result(timeout=remaining + DEADLINE_GRACE_S))
                    if reveal_passes is not None:
                        reveal_passes.append(list(f.reveal_passes))
                return out
            except FleetSaturated as e:
                raise HttpError(503, f"fleet saturated: {e}",
                                headers=retry_after_headers(e)) from e
            except ValueError as e:
                # structurally unservable request (e.g. prompt + budget
                # needs more KV blocks than the arena holds): the client's
                # fault, so 400 — never a 500 (ISSUE 12 satellite)
                raise HttpError(400, str(e)) from e
            except DeadlineExceeded as e:
                raise HttpError(504, f"deadline exceeded: {e}") from e
            except TimeoutError as e:
                # engine wedged past deadline + grace — same contract as a
                # deadline miss, the slot reap just never surfaced
                raise HttpError(504, f"deadline exceeded: {e}") from e
            except RuntimeError as e:
                raise HttpError(503, f"decode engine unavailable: {e}") from e
            finally:
                # this handler is the requests' only consumer: anything not
                # finished when we unwind is abandoned — cancel so the
                # engine frees the slots instead of decoding for nobody
                for f in futs:
                    if not f.done.is_set():
                        f.cancel()
        # Batch-bucket like ServedModel.predict: arbitrary client batch
        # sizes must not mint unbounded XLA compilations.
        n = prompts.shape[0]
        bucket = next((b for b in BATCH_BUCKETS if b >= n), None)
        if bucket is None:
            raise HttpError(413, f"batch of {n} exceeds max {BATCH_BUCKETS[-1]}")
        if bucket != n:
            prompts = np.concatenate([prompts, np.repeat(prompts[:1], bucket - n, axis=0)])
        # Temperature sampling needs a fresh key per request — a fixed key
        # would return the identical sample for identical prompts.
        rng = None
        if self.temperature > 0.0:
            with self._rng_lock:
                self._rng_counter += 1
                counter = self._rng_counter
            # fold_in dispatches device work — keep it outside the lock so
            # concurrent sampled requests don't serialize on it.
            rng = jax.random.fold_in(self._base_rng, counter)
        out = generate(
            self.cfg,
            self.params,
            jnp.asarray(prompts),
            self.max_new_tokens,
            rng=rng,
            temperature=self.temperature,
        )
        return np.asarray(out)[:n].tolist()


def gpt_served_model(
    name: str = "gpt",
    tiny: bool = True,
    max_new_tokens: int = 16,
    temperature: float = 0.0,
    replicas: int = 1,
) -> GenerativeModel:
    """GPT text-generation servable (``tiny`` for CPU CI; ``tiny=False``
    builds the GPT-2-small-class config). ``replicas`` > 1 serves through
    an EngineFleet instead of a single engine."""
    from kubeflow_tpu.models.gpt import GptConfig, GptLM

    cfg = GptConfig.tiny() if tiny else GptConfig.small()
    sample = jnp.zeros((1, 8), jnp.int32)
    params = GptLM(cfg).init(jax.random.PRNGKey(0), sample)["params"]
    return GenerativeModel(
        name=name,
        apply_fn=None,
        params=params,
        cfg=cfg,
        max_new_tokens=max_new_tokens,
        temperature=temperature,
        replicas=replicas,
    )


def bert_served_model(name: str = "bert", tiny: bool = True) -> ServedModel:
    """BERT MLM logits server (the BASELINE 'tf-serving -> JAX BERT' config).

    ``tiny=True`` for CPU CI; ``tiny=False`` builds BERT-base for real
    serving on a chip.
    """
    from kubeflow_tpu.models import BertConfig, BertForMaskedLM

    cfg = BertConfig.tiny() if tiny else BertConfig.base()
    model = BertForMaskedLM(cfg)
    rng = jax.random.PRNGKey(0)
    sample = jnp.zeros((1, 16), jnp.int32)
    params = model.init(rng, sample)["params"]

    def apply_fn(p, ids):
        return model.apply({"params": p}, ids)

    return ServedModel(name=name, apply_fn=apply_fn, params=params, input_dtype=jnp.int32)


def main() -> None:
    """``python -m kubeflow_tpu.serving.server`` — the model-server image
    CMD. The InferenceService controller materializes ``spec.replicas``
    as the ``FLEET_REPLICAS`` env / ``--replicas`` arg, which sizes the
    in-process engine fleet here."""
    import argparse

    from ..runtime.bootstrap import block_forever
    from ..tpu.env import enable_compile_cache

    enable_compile_cache()
    parser = argparse.ArgumentParser(description="JAX model server")
    parser.add_argument("--model",
                        default=os.environ.get("MODEL_NAME", "gpt"))
    parser.add_argument("--port", type=int,
                        default=int(os.environ.get("SERVING_PORT", "8500")))
    parser.add_argument("--replicas", type=int,
                        default=int(os.environ.get("FLEET_REPLICAS", "1")))
    args = parser.parse_args()

    server = ModelServer()
    if args.model == "bert":
        server.add(bert_served_model(name=args.model))
    else:
        server.add(gpt_served_model(name=args.model,
                                    replicas=args.replicas))
    httpd = server.serve(args.port)
    print(f"model-server: {args.model!r} on :{httpd.port} "
          f"(fleet replicas={args.replicas})", flush=True)
    try:
        block_forever()
    finally:
        httpd.close()
        server.close()


if __name__ == "__main__":
    main()
