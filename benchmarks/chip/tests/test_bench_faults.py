"""A run whose timed path is broken underneath comes out not correct: for
each fault a serving cell can have, the harness is driven end to end at
smoke widths (past its look for a chip) with the fault planted in the
program, and ``correct`` must read false."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from smoke import run_smoke

from repro.serving import ServingEngine
from repro.serving.continuous import ContinuousScheduler


def _alter_served_tokens(monkeypatch):
    """A token altered where it is produced: every decode segment hands
    the client each token plus one."""
    for name in ("_segment_full", "_segment_compact"):
        real = getattr(ContinuousScheduler, name)

        def wrapped(self, *a, _real=real, **kw):
            slots, toks, emits, act, eos = _real(self, *a, **kw)
            vocab = self.eng.cfg.vocab_size
            return slots, (np.asarray(toks) + 1) % vocab, emits, act, eos
        monkeypatch.setattr(ContinuousScheduler, name, wrapped)


def _segment_keeps_its_state(monkeypatch):
    """A step that returns its state unchanged: decode segments hand back
    the KV cache they were given, so no decoded token's keys and values
    are ever written."""
    real = ServingEngine._segment_fn

    def segment_fn(self):
        fn = real(self)

        def stale(params, tok, pos, caches, *rest):
            kept = jax.tree.map(jnp.copy, caches)   # the call donates them
            toks, emits, state, _ = fn(params, tok, pos, caches, *rest)
            return toks, emits, state, kept
        return stale
    monkeypatch.setattr(ServingEngine, "_segment_fn", segment_fn)


def _answers_to_the_wrong_request(monkeypatch):
    """An answer altered where it is produced: the encoder's batch hands
    each request its neighbour's logits."""
    real = ServingEngine._encoder_fn

    def encoder_fn(self, bucket):
        fn = real(self, bucket)

        def shifted(params, tokens, mask):
            tag, det = fn(params, tokens, mask)
            return jnp.roll(tag, 1, axis=0), jnp.roll(det, 1, axis=0)
        return shifted
    monkeypatch.setattr(ServingEngine, "_encoder_fn", encoder_fn)


@pytest.mark.parametrize("workload,fault", [
    ("qwen2-0.5b.chat", _alter_served_tokens),
    ("qwen2-0.5b.chat", _segment_keeps_its_state),
    ("gector-base.burst512", _answers_to_the_wrong_request),
], ids=["token_altered", "state_unchanged", "answer_altered"])
def test_fault_is_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    res = run_smoke(workload, seed=4242)
    assert res["correct"] is False, res["checks"]
