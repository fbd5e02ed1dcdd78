"""A run with the timed path broken underneath must come out not
correct.  Each test skips the harness's look for a chip and drives the
rest of a run (``run.run_cell``) at a size a CPU holds, with one fault
planted where the program produces its results."""
import jax
import jax.numpy as jnp

from bench import run
from bench.tests.small import cell
from repro.core import engine


def _run(name):
    c = cell(name)
    res = run.run_cell(c, 2 ** 31 + 11, 1.0, False,
                       jax.devices()[:c["cell"]["chips"]])
    return res


def _campaign_fault(monkeypatch, change):
    orig = engine.dispatch_device

    def broken(kernel, params, keys, n, n_dev):
        out, pad = orig(kernel, params, keys, n, n_dev)
        return change(dict(out), n + pad), pad

    monkeypatch.setattr(engine, "dispatch_device", broken)


def test_sound_runs_are_correct():
    for name in ("v100.campaign", "qwen05.saturated"):
        res = _run(name)
        assert res["correct"], res["compared"]
        assert list(res)[-1] == "compared"


def test_campaign_state_unchanged(monkeypatch):
    # the kernel returns its initial state: nothing simulated
    _campaign_fault(monkeypatch, lambda out, n: {
        k: jnp.zeros_like(v) for k, v in out.items()})
    assert not _run("v100.campaign")["correct"]


def test_campaign_half_the_points_left_out(monkeypatch):
    # the second half of every chunk repeats the first half's results
    def half(out, n):
        h = n // 2
        return {k: jnp.concatenate([v[:h], v[:n - h]]) for k, v in
                out.items()}
    _campaign_fault(monkeypatch, half)
    assert not _run("v100.campaign")["correct"]


def test_campaign_answer_altered(monkeypatch):
    def altered(out, n):
        out["mean_latency"] = out["mean_latency"] * 1.05
        return out
    _campaign_fault(monkeypatch, altered)
    assert not _run("v100.campaign")["correct"]


def test_campaign_accumulator_altered(monkeypatch):
    from repro.core import campaign as camp
    orig = camp.campaign

    def altered(*a, **kw):
        res = orig(*a, **kw)
        res.acc["sum_latency"] = res.acc["sum_latency"] * (1 + 1e-6)
        return res
    monkeypatch.setattr(camp, "campaign", altered)
    assert not _run("v100.campaign")["correct"]


def _serve_fault(monkeypatch, change):
    # under the benchmark's tap: the engine's own compiled call is broken
    from repro.serving import InferenceEngine
    orig = InferenceEngine._build_fns

    def build(self):
        orig(self)
        self._fns = {b: (lambda fn: lambda p, batch: change(
            fn(p, batch)))(fn) for b, fn in self._fns.items()}
    monkeypatch.setattr(InferenceEngine, "_build_fns", build)


def test_serve_token_altered(monkeypatch):
    _serve_fault(monkeypatch, lambda out: out.at[:, 2].set(
        (out[:, 2] + 1) % 512))
    assert not _run("qwen05.saturated")["correct"]


def test_serve_half_the_batch_left_out(monkeypatch):
    def half(out):
        h = out.shape[0] // 2
        return jnp.concatenate([out[:h], out[:out.shape[0] - h]])
    _serve_fault(monkeypatch, half)
    assert not _run("qwen05.saturated")["correct"]


def test_serve_step_returns_its_state_unchanged(monkeypatch):
    from repro.models import transformer as tfm
    orig = tfm.decode_step

    def stale(cfg, params, tokens, cache, lengths, **kw):
        logits, _ = orig(cfg, params, tokens, cache, lengths, **kw)
        return logits, cache          # the KV cache never advances
    monkeypatch.setattr(tfm, "decode_step", stale)
    assert not _run("qwen05.saturated")["correct"]
