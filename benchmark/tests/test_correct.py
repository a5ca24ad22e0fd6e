"""``correct`` has been shown to fail: the lower-precision control and each
fault a cell can have, driven through the runners at toy size on the CPU
(the harness's look for a chip is skipped; the rest of a run is real)."""

import time

import jax
import numpy as np
import pytest

from benchmark import correct, harness
from benchmark.reference import gpt as ref_g
from benchmark.runners import composite_train, gpt_serve, gpt_train

from . import toy


def verdict(outcome):
    return all(v <= lim for _, v, lim in outcome.checks), dict(
        (n, v) for n, v, _ in outcome.checks)


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"a": np.array([1.0, 1.0, 1.0]), "b": np.array(1e-9)}
    prog = {"a": np.array([1.0, 1.1, 1.0]), "b": np.array(2e-9)}
    gap, name = correct.worst_leaf_gap(prog, ref)
    assert name == "a[1]" and gap == pytest.approx(0.1)
    assert correct.negligible_leaves(ref).tolist() == [False, False, False, True]
    assert correct.worst_leaf_gap({"a": np.array([np.nan] * 3), "b": np.array(0.0)}, ref)[0] == np.inf


def test_gpt_train_sound_run_is_correct_and_reports_the_contract_line(capsys):
    cell = toy.cell("gpt_train")
    out = gpt_train.run(cell, jax.devices()[:1], time.perf_counter())
    ok, values = verdict(out)
    assert ok, values
    harness.emit(cell, out, jax.devices()[:1])
    import json

    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks" and line["correct"] is True
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(line["checks"]) == {"loss_gap", "grad_norm_gap", "change_norm_gap"}


def test_gpt_train_control_at_fp8_is_not_correct():
    cell = toy.cell("gpt_train")
    sizes = gpt_train.sizes_of(cell.config)
    batches = gpt_train.trainloop.first_batches(cell, sizes["vocab_size"])
    opt = cell.deploy["optimizer"]
    reference = gpt_train.reference_readings(cell.seed, sizes, batches, opt)
    control = gpt_train.reference_readings(cell.seed, sizes, batches, opt, cast=ref_g.fp8_cast)
    checks = correct.train_checks(control, reference, cell.limits)
    assert any(v > lim for _, v, lim in checks), checks


def test_gpt_train_state_left_unchanged_is_not_correct(monkeypatch):
    def stuck(self, batch):
        params, opt_state = self.params, self.opt_state
        import jax.numpy as jnp

        keep = jax.tree_util.tree_map(jnp.copy, (params, opt_state))
        _, _, loss = self.step(params, opt_state, batch)
        self.params, self.opt_state = keep
        return loss

    monkeypatch.setattr(gpt_train.Program, "__call__", stuck)
    monkeypatch.setattr(gpt_train.Program, "first_gradient_norms",
                        lambda self: {k: np.zeros(()) for k in gpt_train.LEAVES}
                        | {k: np.zeros(2) for k in gpt_train.LEAVES
                           if k not in ("embedding", "ln_final_scale", "ln_final_bias")})
    out = gpt_train.run(toy.cell("gpt_train"), jax.devices()[:1], time.perf_counter())
    ok, values = verdict(out)
    assert not ok and values["change_norm_gap"] == pytest.approx(1.0)


def test_gpt_train_half_the_batch_left_out_is_not_correct(monkeypatch):
    feed = gpt_train.Program.feed

    def half(self, i):
        batch = np.array(feed(self, i))
        batch[len(batch) // 2:] = batch[:len(batch) // 2]   # the mean over the rest
        return jax.device_put(batch)

    monkeypatch.setattr(gpt_train.Program, "feed", half)
    out = gpt_train.run(toy.cell("gpt_train"), jax.devices()[:1], time.perf_counter())
    ok, values = verdict(out)
    assert not ok and values["grad_norm_gap"] > 0.1, values


def test_composite_sound_run_is_correct():
    out = composite_train.run(toy.cell("composite_train"), jax.devices()[:4], time.perf_counter())
    ok, values = verdict(out)
    assert ok, values
    assert out.extra["collectives_in_program"] > 0


def test_composite_control_at_fp8_is_not_correct():
    cell = toy.cell("composite_train")
    sizes = composite_train.sizes_of(cell)
    batches = composite_train.trainloop.first_batches(cell, sizes["vocab_size"])
    dev = jax.devices()[0]
    reference = composite_train.reference_readings(cell.seed, sizes, batches, 1e-2, dev)
    control = composite_train.reference_readings(cell.seed, sizes, batches, 1e-2, dev,
                                                 cast=ref_g.fp8_cast)
    checks = correct.train_checks(control, reference, cell.limits)
    assert any(v > lim for _, v, lim in checks), checks


def test_composite_exchange_between_chips_left_out_is_not_correct(monkeypatch):
    from kubeflow_tpu.parallel import composite

    class Lax:
        def __getattr__(self, name):
            if name == "psum":
                return lambda x, axis: x
            return getattr(jax.lax, name)

    monkeypatch.setattr(composite, "lax", Lax())
    out = composite_train.run(toy.cell("composite_train"), jax.devices()[:4], time.perf_counter())
    ok, values = verdict(out)
    assert not ok, values


def test_composite_half_the_batch_left_out_is_not_correct(monkeypatch):
    feed = composite_train.Program.feed

    def half(self, i):
        batch = np.array(feed(self, i))
        batch[:, 1:] = batch[:, :1]
        return jax.device_put(batch, self.batch_sharding)

    monkeypatch.setattr(composite_train.Program, "feed", half)
    out = composite_train.run(toy.cell("composite_train"), jax.devices()[:4], time.perf_counter())
    ok, values = verdict(out)
    assert not ok, values


def test_gpt_serve_sound_run_is_correct_and_an_altered_token_is_not(monkeypatch):
    cell = toy.cell("gpt_serve", seconds=2.0)
    out = gpt_serve.run(cell, jax.devices()[:1], time.perf_counter())
    ok, values = verdict(out)
    assert ok and out.attempted > 5 and out.failed == 0, values
    assert out.obs["tokens_out_in_window"] > 0

    post = gpt_serve.Drive._post

    def altered(self, i):
        post(self, i)
        reply = self.replies[i]
        if reply is not None:      # one token changed where it is produced
            at = len(self.arrivals[i].prompt) + 3
            reply[at] = (reply[at] + 1) % 2048

    monkeypatch.setattr(gpt_serve.Drive, "_post", altered)
    out = gpt_serve.run(cell, jax.devices()[:1], time.perf_counter())
    ok, values = verdict(out)
    assert not ok and values["served_logit_gap_sd"] > 0.5, values


def test_gpt_serve_control_at_fp8_reads_a_gap():
    """The control need not decode: at the served positions, the gap of the
    token the lower precision puts first."""
    cell = toy.cell("gpt_serve", seconds=1.5)
    server = gpt_serve.Server(cell, jax.devices()[:1])
    from benchmark import traffic

    drive = gpt_serve.Drive(server, traffic.arrivals(cell.mix, 2048, cell.seed, cell.seconds),
                            cell.seconds)
    drive.run()
    picks = gpt_serve.sample_requests(drive, cell.seed, 12)
    longest = max(range(len(drive.replies)), key=lambda i: len(drive.replies[i] or []))
    assert picks[0] == longest and len(set(picks)) == len(picks)
    server.close()
    served, control = gpt_serve.served_gaps(drive, picks, cell.seed, server.sizes,
                                            cast=ref_g.fp8_cast)
    assert served.shape == control.shape == (len(picks), 8)
    assert control.max() > served.max(), (control.max(), served.max())
