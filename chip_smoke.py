"""Chip smoke: the platform's main paths, once, at full width, on the TPU.

    python chip_smoke.py             # one chip: serve, train, hpo
    python chip_smoke.py --chips 4   # the composite step on a 2x2 mesh, only

One process, the entry points a user calls, random weights from ``--seed``,
depth and widths as the bench rows have them. Every phase checks its own
output (greedy tokens against the ``generate()`` oracle, losses finite and
falling, Pallas calls present in the compiled text, zero fused fallbacks,
sharded loss against the one-device loss) and any failure ends the run with
the traceback and a non-zero exit code: no phase is caught and skipped, and
without a TPU nothing is built at all.

Each phase prints one JSON line (wall seconds, the compiler's share of them,
persistent-cache hits and misses, peak device memory); the last line of
stdout is ``{"ok": true, "device": {...}}`` as JAX reports the device.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import re
import sys
import threading
import time
import urllib.request
from typing import Any, Callable, Dict, List

PALLAS_CALL = 'custom_call_target="tpu_custom_call"'
# JAX's own monitoring events (jax/_src/dispatch.py, compiler.py)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def sizes() -> Dict[str, Any]:
    """What "full width" means, in one place: the widths of the bench rows.
    (The CPU rehearsal in tests/ swaps this for toy sizes.)"""
    import bench
    from kubeflow_tpu.models.bert import BertConfig
    from kubeflow_tpu.models.gpt import GptConfig
    from kubeflow_tpu.parallel.composite import CompositeConfig

    return {
        # serving: every serving row's model, the defaults users get
        "serve_gpt": GptConfig.base(),          # 24L x 1024, 16 heads
        "serve_new_tokens": 32,
        "serve_bert": BertConfig(),             # BERT-base
        "serve_bert_shape": (8, 128),           # e2e/serving_bench.py's row
        # training: the two bench rows' programs
        "gpt_train": bench.gpt_train_config(seq=1024),
        "gpt_batch": 8,
        "gpt_min_pallas_calls": 3,              # flash fwd + bwd (dq, dkv)
        "resnet_step": lambda: bench.resnet_train_step(fused=True),
        "resnet_batch": (256, 224),
        "resnet_pallas_calls": 16,              # one per bottleneck block
        # hpo: trials through the real StudyJob controller
        "hpo_trials": 2,
        # four chips: GPT-medium widths and depth. The one-device run it is
        # compared with is what bound when these sizes were chosen: f32,
        # materialized scores and every intermediate of a block kept
        # compiled to 14.05 of 15.75 GiB there at one microbatch of two
        # sequences (4.8 GiB a device on the 2x2 mesh). Since the block's
        # checkpoint policy (composite._remat) both need far less.
        "composite": CompositeConfig(vocab_size=32000, d_model=1024,
                                     n_heads=16, d_ff=4096, n_layers=24,
                                     seq=1024),
        "composite_batch": (1, 2),              # microbatches x sequences
        # plain SGD on this init: 0.1 (the toy default) diverges at this
        # width within two steps, and a diverging run amplifies the
        # rounding the comparison is meant to bound
        "composite_lr": 1e-4,
    }


class CompileMeter:
    """JAX's own compile events, summed for the phase in progress: seconds
    in the XLA compiler, seconds reading executables back from the
    persistent cache, and the cache's hits and misses. A miss is an entry written: a program that compiles in under the
    cache's one-second threshold is neither, and compiles again every run."""

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self._lock = threading.Lock()
        self.reset()
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def reset(self) -> None:
        with self._lock:
            self.compile_s = self.load_s = 0.0
            self.hits = self.misses = 0

    def _on_duration(self, event: str, duration: float, **_: Any) -> None:
        with self._lock:
            if event == BACKEND_COMPILE_EVENT:  # includes a hit's load
                self.compile_s += duration
            elif event == CACHE_LOAD_EVENT:
                self.load_s += duration

    def _on_event(self, event: str, **_: Any) -> None:
        with self._lock:
            if event == CACHE_HIT_EVENT:
                self.hits += 1
            elif event == CACHE_MISS_EVENT:
                self.misses += 1


def run_phase(name: str, fn: Callable[..., Dict[str, Any]], meter: CompileMeter,
              device: Any, *args: Any) -> None:
    """Run one phase and print its line. Exceptions propagate: a failed
    phase is the end of the run."""
    gc.collect()
    stats = device.memory_stats() or {}
    before = stats.get("bytes_in_use")
    meter.reset()
    t0 = time.perf_counter()
    detail = fn(*args)
    wall = time.perf_counter() - t0
    stats = device.memory_stats() or {}
    print(json.dumps({
        "phase": name, "wall_s": round(wall, 2),
        "compile_s": round(meter.compile_s - meter.load_s, 2),
        "cache_load_s": round(meter.load_s, 2),
        # the rest: tracing, lowering, execution, host work. Compiles on
        # other threads (the engine's worker, parallel trials) overlap the
        # wall clock, so there it is a floor.
        "run_s": round(max(0.0, wall - meter.compile_s), 2),
        "cache_hits": meter.hits, "cache_misses": meter.misses,
        "bytes_in_use_before": before,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        **detail}), flush=True)


def _post(url: str, body: Dict[str, Any], timeout: float) -> Dict[str, Any]:
    req = urllib.request.Request(
        url, json.dumps(body).encode(), {"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


#: How far below the top logit a greedy token may sit, in standard deviations
#: of that position's logits, and still count as the argmax. The chip's bf16
#: rounding depends on the shape a program runs at: at 24L x 1024 the plain
#: forward, ``generate()`` at batch 1 and ``generate()`` at batch 8 pick
#: different tokens where the top two logits are closer than ~0.025 sd (my
#: chip runs, PR 21), and such near-ties turn up about once in 128 random-
#: weight tokens. A wrong token (wrong row, wrong position, stale block) sits
#: sd's away.
TIE_SD = 0.1


def _reference_margins(gcfg: Any, params: Any, served: List[List[int]],
                       oracle: List[List[int]]) -> Any:
    """Score next tokens against a plain forward over the served sequences:
    no KV cache, no engine, flash attention as in training. Returns two
    [n, width - 1] arrays — for ``served`` and for ``oracle`` — whose entry
    ``t`` says how far (in sd of position t's logits) that sequence's token
    ``t + 1`` sits under the argmax there; 0.0 = the argmax. The oracle's
    entries mean something only as far as its prefix is the served one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.gpt import GptLM

    width = -(-max(map(len, served)) // 128) * 128

    def padded(sequences):   # causal: the padding cannot reach back
        return jnp.asarray([seq + [0] * (width - len(seq))
                            for seq in sequences], jnp.int32)

    @jax.jit
    def score(p, ids, other):
        logits = GptLM(gcfg).apply({"params": p}, ids)[:, :-1]
        top, sd = logits.max(-1), logits.std(-1)

        def under(nxt):
            pick = jnp.take_along_axis(logits, nxt[:, 1:, None], -1)[..., 0]
            return (top - pick) / sd

        return under(ids), under(other)

    return tuple(np.asarray(m) for m in
                 score(params, padded(served), padded(oracle)))


def phase_serve(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """ModelServer over real HTTP: concurrent generations against the
    ``generate()`` oracle and a plain forward, KV blocks drained, then one
    BERT predict."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.bert import BertForMaskedLM
    from kubeflow_tpu.models.gpt import GptLM, generate
    from kubeflow_tpu.runtime.metrics import METRICS
    from kubeflow_tpu.serving.continuous import PREFILL_BUCKETS
    from kubeflow_tpu.serving.server import (
        GenerativeModel, ModelServer, ServedModel)

    gcfg, budget = cfg["serve_gpt"], cfg["serve_new_tokens"]
    rng = jax.random.PRNGKey(seed)
    params = jax.jit(GptLM(gcfg).init)(
        rng, jnp.zeros((1, 8), jnp.int32))["params"]
    # four prompts spread over the prefill buckets that leave room to decode
    fit = [b for b in PREFILL_BUCKETS if b + budget <= gcfg.max_seq]
    lengths = [b - b // 4 for b in (fit * 4)[-4:]]
    np_rng = np.random.default_rng(seed)
    prompts = [np_rng.integers(1, gcfg.vocab_size, size=n).tolist()
               for n in lengths]
    oracle = [np.asarray(generate(gcfg, params, np.asarray([p], np.int32),
                                  max_new_tokens=budget))[0].tolist()
              for p in prompts]

    bcfg, (bb, bseq) = cfg["serve_bert"], cfg["serve_bert_shape"]
    bert = BertForMaskedLM(bcfg)
    bert_ids = np_rng.integers(0, bcfg.vocab_size, (bb, bseq))
    bert_params = jax.jit(bert.init)(rng, jnp.asarray(bert_ids[:1]))["params"]

    def bert_apply(p, ids):
        return jnp.argmax(bert.apply({"params": p}, ids), -1).astype(jnp.int32)

    # continuous=True, paged=True: the defaults, not restated
    model = GenerativeModel(name="gpt", apply_fn=None, params=params,
                            cfg=gcfg, max_new_tokens=budget)
    server = ModelServer()
    server.add(model)
    server.add(ServedModel(name="bert", apply_fn=bert_apply,
                           params=bert_params, input_dtype=jnp.int32))
    httpd = server.serve(0)
    base = f"http://127.0.0.1:{httpd.port}/v1/models"
    try:
        got: List[Any] = [None] * len(prompts)

        def client(i: int) -> None:
            try:
                got[i] = _post(f"{base}/gpt:predict",
                               {"instances": [prompts[i]]}, 900.0)
            except Exception as e:  # surfaced by the comparison below
                got[i] = e

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900.0)
        if any(t.is_alive() for t in threads):
            raise RuntimeError("serve: a predict request hung")
        for reply in got:
            if isinstance(reply, Exception):
                raise reply
        served = [reply["predictions"][0] for reply in got]
        # Every token the server returned is the greedy choice of a plain
        # forward over its own prefix, and the server follows the oracle
        # token for token until (if ever) the two split on a near-tie.
        under, oracle_under = _reference_margins(gcfg, params, served, oracle)
        worst = max(float(under[i, n - 1:len(seq) - 1].max())
                    for i, (seq, n) in enumerate(zip(served, lengths)))
        if worst > TIE_SD:
            raise AssertionError(
                f"serve: a served token sits {worst:.3f} sd under the "
                f"reference argmax (limit {TIE_SD})")
        splits: List[Any] = []
        for i, (have, want) in enumerate(zip(served, oracle)):
            split = next((j for j, (a, b) in enumerate(zip(have, want))
                          if a != b), None)
            splits.append(split)
            if split is not None and oracle_under[i, split - 1] > TIE_SD:
                raise AssertionError(
                    f"serve: prompt {i} (len {lengths[i]}) leaves the "
                    f"generate() oracle at position {split}, where the "
                    f"oracle's token sits {oracle_under[i, split - 1]:.3f} sd "
                    f"under the argmax — no tie: "
                    f"{have[split:split + 4]} vs {want[split:split + 4]}")
        deadline = time.monotonic() + 30.0
        while METRICS.total("serving_kv_blocks_used") != 0.0:
            if time.monotonic() > deadline:
                raise AssertionError("serve: KV blocks did not drain to 0")
            time.sleep(0.05)
        if not METRICS.total("serving_kv_blocks_free") > 0:
            raise AssertionError("serve: paged arena gauges missing")

        out = _post(f"{base}/bert:predict",
                    {"instances": bert_ids.tolist()}, 600.0)["predictions"]
        want = np.asarray(jax.jit(bert_apply)(bert_params,
                                              jnp.asarray(bert_ids)))
        if not np.array_equal(np.asarray(out), want):
            raise AssertionError("serve: BERT predict differs from apply_fn")
    finally:
        httpd.close()
        server.close()
        model.close()
    return {"gpt_prompt_lens": lengths, "gpt_new_tokens": budget,
            "gpt_worst_margin_sd": round(worst, 4),
            "gpt_left_oracle_at": splits, "bert_batch": [bb, bseq]}


def _assert_no_fallback(what: str) -> None:
    from kubeflow_tpu.runtime.metrics import METRICS

    n = METRICS.total("ops_fused_fallback_total")
    if n:
        raise AssertionError(
            f"{what}: {n:g} fused kernel(s) gave way to the XLA reference "
            "(ops_fused_fallback_total)")


def phase_train_gpt(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The GPT row's step (blockwise loss, AdamW), three steps on one batch."""
    import jax
    import optax

    import bench
    from kubeflow_tpu.training.flops import memory_stats

    gcfg, batch = cfg["gpt_train"], cfg["gpt_batch"]
    opt = optax.adamw(3e-4, weight_decay=0.01)
    model, train_step = bench.gpt_train_step(gcfg, opt)
    rng = jax.random.PRNGKey(seed)
    ids = jax.random.randint(rng, (batch, gcfg.max_seq), 0, gcfg.vocab_size)
    params = jax.jit(model.init)(rng, ids)["params"]
    opt_state = jax.jit(opt.init)(params)
    compiled = jax.jit(train_step).lower(params, opt_state, ids).compile()
    calls = compiled.as_text().count(PALLAS_CALL)
    memory = memory_stats(compiled)
    if calls < cfg["gpt_min_pallas_calls"]:
        raise AssertionError(
            f"train_gpt: {calls} Pallas calls in the compiled step, want "
            f">= {cfg['gpt_min_pallas_calls']} (flash fwd + bwd)")
    losses = []
    for _ in range(3):
        params, opt_state, loss = compiled(params, opt_state, ids)
        losses.append(float(loss))
    if not (all(math.isfinite(x) for x in losses) and losses[-1] < losses[0]):
        raise AssertionError(f"train_gpt: loss not finite and falling: {losses}")
    _assert_no_fallback("train_gpt")
    return {"losses": losses, "pallas_calls": calls, "program": memory,
            "scan_blocks": gcfg.scan_blocks, "remat": gcfg.remat}


def phase_train_resnet(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The ResNet-50 row's step on the fused blocks, three steps."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.training.flops import memory_stats

    batch, image = cfg["resnet_batch"]
    task, step = cfg["resnet_step"]()
    rng = jax.random.PRNGKey(seed)
    images = jax.random.normal(rng, (batch, image, image, 3), jnp.float32)
    labels = jax.random.randint(rng, (batch,), 0, 1000)
    state = jax.jit(task.init)(rng, images)
    compiled = step.lower(state, images, labels).compile()
    calls = compiled.as_text().count(PALLAS_CALL)
    memory = memory_stats(compiled)
    if calls != cfg["resnet_pallas_calls"]:
        raise AssertionError(
            f"train_resnet: {calls} Pallas calls in the compiled step, want "
            f"{cfg['resnet_pallas_calls']} block kernels")
    losses = []
    for _ in range(3):
        state, metrics = compiled(state, images, labels)
        losses.append(float(metrics["loss"]))
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"train_resnet: loss not finite: {losses}")
    _assert_no_fallback("train_resnet")
    return {"losses": losses, "pallas_calls": calls, "program": memory}


def rebuild_native_store() -> str:
    """The store core is built from committed files only: drop whatever
    ``.so`` the tree carries (untracked, so possibly stale or foreign) and
    let the apiserver's own build make it from ``store_core.cc``. Returns
    what the control plane will run on, and why."""
    from kubeflow_tpu.apiserver import backend

    if os.path.exists(backend._SO_PATH):
        os.remove(backend._SO_PATH)
    try:
        backend._build_native()
        why = "rebuilt from store_core.cc"
    except backend.NativeUnavailable as e:
        why = f"native core unavailable: {e}"
    return f"{type(backend.default_backend()).__name__} ({why})"


def phase_hpo(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """MNIST trials through the real StudyJob controller, in-process: the
    one place the control plane itself puts work on the chip."""
    from e2e.studyjob_driver import run_studyjob_e2e

    store = rebuild_native_store()
    n = cfg["hpo_trials"]
    status = run_studyjob_e2e("mnist", max_trials=n, parallel=n, timeout=600.0)
    best = status["currentOptimalTrial"]["observation"]["accuracy"]
    if status["trialsSucceeded"] != n or not math.isfinite(best):
        raise AssertionError(f"hpo: study did not complete cleanly: {status}")
    return {"store_backend": store, "trials": n, "best_accuracy": best}


def phase_multichip(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """``composite.make_train_step`` on a {fsdp: 2, model: 2} mesh over all
    four chips against the same init and batch on a one-device mesh."""
    import jax
    import numpy as np

    from kubeflow_tpu.models.gpt import GptConfig, GptLM
    from kubeflow_tpu.parallel import MeshConfig, composite, make_mesh
    from kubeflow_tpu.serving.fleet import EngineFleet

    devices = jax.devices()[:4]
    if len(devices) != 4:
        raise RuntimeError(f"--chips 4 needs four devices, have {len(devices)}")

    # where a fleet's replicas land: nothing in serving/ names a device
    tiny = GptConfig.tiny()
    fleet = EngineFleet(
        tiny, jax.jit(GptLM(tiny).init)(jax.random.PRNGKey(seed),
                                        np.zeros((1, 8), np.int32))["params"],
        replicas=4, slots=2, register_debug=False)
    try:
        placement = {h.id: sorted({d.id for leaf in
                                   jax.tree_util.tree_leaves(h.engine.cache)
                                   for d in leaf.devices()})
                     for h in fleet.live_handles()}
    finally:
        fleet.close()
    print(json.dumps({"fleet_replica_devices": placement}), flush=True)

    ccfg, (micro, mb) = cfg["composite"], cfg["composite_batch"]
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1),
                             (micro, mb, ccfg.seq), 0, ccfg.vocab_size)

    def run(mesh) -> Dict[str, Any]:
        params = composite.init_params(jax.random.PRNGKey(seed), ccfg, mesh)
        batch = jax.device_put(ids, composite.batch_sharding(mesh))
        # the state's bytes on each device, from the shards themselves and
        # as the device's allocator counts them
        resident = [sum(shard.data.nbytes
                        for leaf in jax.tree_util.tree_leaves(params)
                        for shard in leaf.addressable_shards
                        if shard.device == d) for d in devices]
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
        step = composite.make_train_step(
            ccfg, mesh, lr=cfg["composite_lr"]).lower(params, batch).compile()
        text = step.as_text()
        losses = []
        for _ in range(3):
            params, loss = step(params, batch)
            losses.append(float(loss))
        return {"losses": losses, "state_bytes_per_device": resident,
                "bytes_in_use_per_device": in_use,
                "collectives": {
                    op: len(re.findall(rf" {op}(?:-start)?\(", text))
                    for op in ("all-gather", "all-reduce", "reduce-scatter",
                               "all-to-all", "collective-permute")}}

    sharded = run(make_mesh(MeshConfig(data=1, fsdp=2, model=2),
                            devices=devices))
    gc.collect()
    single = run(make_mesh(MeshConfig(), devices=devices[:1]))
    # tests/test_composite.py::test_factorizations_are_equivalent's tolerance
    np.testing.assert_allclose(sharded["losses"], single["losses"], rtol=2e-4)
    losses = sharded["losses"]
    if not (all(math.isfinite(x) for x in losses) and losses[-1] < losses[0]):
        raise AssertionError(
            f"multichip: loss not finite and falling: {losses}")
    if not sum(sharded["collectives"].values()):
        raise AssertionError("multichip: no collective in the sharded step")
    per_dev = sharded["state_bytes_per_device"]
    if min(per_dev) < 0.5 * max(per_dev):
        raise AssertionError(
            f"multichip: state is not spread over the four chips: {per_dev}")
    return {"mesh": {"fsdp": 2, "model": 2}, "n_layers": ccfg.n_layers,
            "sharded": sharded, "one_device": single}


def main(argv: Any = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 = the composite step on a 2x2 mesh, only")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    from kubeflow_tpu.tpu.env import enable_compile_cache, require_tpu

    device = require_tpu()  # phase "device": no TPU, no run
    cache_dir = enable_compile_cache()
    import jax
    import jaxlib

    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    report = {"platform": device.platform, "kind": device.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"phase": "device", **report, "jax": jax.__version__,
                      "jaxlib": jaxlib.__version__, "libtpu": libtpu,
                      "compile_cache_dir": cache_dir,
                      "cache_dir_from_env":
                          bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))}),
          flush=True)

    cfg = sizes()
    meter = CompileMeter()
    phases = ([("multichip", phase_multichip)] if args.chips == 4 else
              [("serve", phase_serve), ("train_gpt", phase_train_gpt),
               ("train_resnet", phase_train_resnet), ("hpo", phase_hpo)])
    for name, fn in phases:
        run_phase(name, fn, meter, device, cfg, args.seed)
    print(json.dumps({"ok": True, "device": report}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
