"""A run whose timed path is broken underneath comes out not correct, once
for each fault a cell can have (one chip: no exchange between chips):
a train step that returns its state unchanged; half of each batch left
out, the mean taken over the rest; a token altered where it is produced."""
import numpy as np
import pytest

import bench_tiny


def _unchanged_state(rt):
    orig = rt._train_step_for

    def step_for(spec):
        real = orig(spec)

        def step(base, lora, opt, batch):
            _, _, metrics = real(base, lora, opt, batch)
            return lora, opt, metrics
        return step
    rt._train_step_for = step_for


def _half_batch(rt):
    orig = rt._train_step_for

    def step_for(spec):
        real = orig(spec)

        def step(base, lora, opt, batch):
            m = np.array(batch["loss_mask"], copy=True)
            m[m.shape[0] // 2:] = 0.0
            return real(base, lora, opt, dict(batch, loss_mask=m))
        return step
    rt._train_step_for = step_for


@pytest.fixture
def altered_tokens(monkeypatch):
    """Every 5th sampled token is recorded one id higher than sampled (its
    logprob stays that of the sampled token)."""
    import repro.rollout.engine as eng
    orig = eng._Row.accept

    def accept(self, token, lp, mask, max_total):
        if mask == 1.0 and token > 10 and self.sampled % 5 == 4:
            token = token + 1
        return orig(self, token, lp, mask, max_total)
    monkeypatch.setattr(eng._Row, "accept", accept)


@pytest.mark.parametrize("fault,fails", [
    (_unchanged_state, ("train_grad_gap", "train_update_gap")),
    (_half_batch, ("train_loss_gap", "train_grad_gap")),
])
def test_trainer_faults_are_caught(fault, fails):
    line = bench_tiny.run(11, fault=fault)
    assert line["correct"] is False
    bad = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert set(fails) <= bad, line["checks"]
    assert type(line["attempted"]) is int and line["attempted"] >= 1


def test_altered_token_is_caught(altered_tokens):
    line = bench_tiny.run(12)
    assert line["correct"] is False
    c = line["checks"]["lp_gap"]
    assert c["value"] > c["limit"], line["checks"]
