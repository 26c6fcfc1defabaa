"""Faults planted in the timed path under a whole benchmark run (past the
device check) must come out as not correct: a decoded token altered where
it is produced, a decode step that returns its cache unchanged, and half
of each prompt left out of prefill. Tiny cells on the CPU. (The cells are
one chip each and the replica pool exchanges nothing between chips, so
there is no exchange to leave out.)"""
import jax.numpy as jnp
import pytest

from bench_tiny import run_tiny


def _alter_tokens(monkeypatch):
    """A decoded token altered where it is produced: the sampler returns
    the id after the best one."""
    from repro.serve import decode
    real = decode._sample_tokens

    def wrong(logits, key, **kw):
        return (real(logits, key, **kw) + 1) % logits.shape[-1]
    monkeypatch.setattr(decode, "_sample_tokens", wrong)


def _state_unchanged(monkeypatch):
    """The decode step returns its cache unchanged: no key or value of a
    decoded token is ever written."""
    from repro.serve import decode
    monkeypatch.setattr(decode, "_local_write", lambda cache, row, rel: cache)
    monkeypatch.setattr(decode, "_paged_write",
                        lambda pool, row, pt, pos, i, msize: pool)


def _half_prompt(monkeypatch):
    """Half of each prompt left out of prefill (its first half zeroed)."""
    from repro.serve import engine
    real = engine.prefill

    def half(cfg, params, toks, ctx, **kw):
        pl = kw["prompt_len"]
        keep = jnp.arange(toks.shape[1])[None, :] >= (pl[:, None] // 2)
        return real(cfg, params, jnp.where(keep, toks, 0), ctx, **kw)
    monkeypatch.setattr(engine, "prefill", half)


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged,
                                   _half_prompt],
                         ids=["token-altered", "state-unchanged",
                              "half-prompt"])
@pytest.mark.parametrize("config", ["tiny-nemo", "tiny-danube"])
def test_fault_in_timed_path_is_not_correct(monkeypatch, config, fault):
    fault(monkeypatch)
    out = run_tiny(monkeypatch, config, seed=11)
    assert not out["correct"], out["checks"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
