"""The check fails a run whose timed path is broken underneath: the
harness runs as on the chip (past its look for one), on the CPU at smoke
sizes, with the engine broken in each way a served cell can be."""

import jax
import jax.numpy as jnp
import pytest
import test_harness

from repro.engine import SOIEngine, api
from soibench import serve


def _altered_tokens(monkeypatch):
    """A token altered where it is produced: every generate step's
    tokens, as drained, are off by one."""
    real = SOIEngine.generate

    def generate(self, params, state):
        state, res = real(self, params, state)
        data = res.data.at[:, 0].set((res.data[:, 0] + 1) % self.cfg.vocab)
        return state, api.ResultTokens(data=data, logits=res.logits,
                                       metrics=res.metrics)

    monkeypatch.setattr(SOIEngine, "generate", generate)


def _state_unchanged(monkeypatch):
    """A step that returns its state unchanged: caches, clocks and next
    tokens stay as they were before the step."""
    real = serve.make_engine

    def make_engine(cfg, mix):
        engine = real(cfg, mix)
        step = engine._gen._fun
        engine._gen = jax.jit(lambda p, ds: (ds,) + step(p, ds)[1:])
        return engine

    monkeypatch.setattr(serve, "make_engine", make_engine)


@pytest.mark.parametrize("fault", [_altered_tokens, _state_unchanged])
@pytest.mark.parametrize("workload", test_harness.CELLS)
def test_broken_path_is_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    out, _ = test_harness.run(workload)
    assert out["correct"] is False
    assert out["check"]["max_gap"]["value"] > test_harness.SMOKE_LIMIT


def test_unbroken_control_for_the_faults():
    out, _ = test_harness.run(test_harness.CELLS[0])
    assert out["correct"] is True
    assert jnp.isfinite(out["check"]["max_gap"]["value"])
