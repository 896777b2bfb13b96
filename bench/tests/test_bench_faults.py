"""Each fault a training cell on one chip can have, planted in the
program under the harness, makes `correct` come out false: a step that
returns its state unchanged, half of each row left out of the mean, and
one token altered where the batch is made."""
import pytest

import repro.data.pipeline as pipeline
import repro.train.loop as loop
import repro.train.step as step_mod
from bench_tiny import run_tiny


def frozen(monkeypatch):
    orig = loop.make_train_step

    def make(cfg, run):
        step = orig(cfg, run)

        def train_step(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return train_step
    monkeypatch.setattr(loop, "make_train_step", make)


def half_batch(monkeypatch):
    orig = step_mod.cross_entropy

    def cross_entropy(logits, labels, z_weight=0.0):
        half = labels.shape[1] // 2
        return orig(logits[:, :half], labels[:, :half], z_weight)
    monkeypatch.setattr(step_mod, "cross_entropy", cross_entropy)


def altered_token(monkeypatch):
    orig = pipeline.lm_batch

    def lm_batch(key, batch, seq_len, vocab):
        tokens, labels = orig(key, batch, seq_len, vocab)
        i = seq_len // 2
        return tokens.at[:, i].set((tokens[:, i] + 1) % vocab), labels
    monkeypatch.setattr(pipeline, "lm_batch", lm_batch)


@pytest.mark.parametrize("fault", [frozen, half_batch, altered_token],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = run_tiny("stablelm-12b.sketch-s2048")
    assert not out["correct"], out["checks"]
