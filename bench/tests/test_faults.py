"""A run whose timed path is broken underneath comes out not correct.

The serving cells can have two of the listed faults: an answer altered
where it is produced, and (in a batch of several images) half of the batch
left out, its rows filled from the other half.  A request that never gets
its answer is a fault too.  (A step that returns its state unchanged and
the exchange between chips are faults of training and of sharded cells;
none of these cells has them.)"""
import jax.numpy as jnp
import pytest

from conftest import CELLS


def _alter_image(monkeypatch):
    from repro.core.darknet.network import CompileCache
    run = CompileCache.run

    def altered(self, x):
        y = run(self, x)
        return y.at[0, 0].add(1e-3)   # one probability of each batch's first

    monkeypatch.setattr(CompileCache, "run", altered)


def _half_batch(monkeypatch):
    from repro.core.darknet.network import CompileCache
    run = CompileCache.run

    def halved(self, x):
        half = x.shape[0] // 2
        y = run(self, x[:half])
        return jnp.concatenate([y, y[:x.shape[0] - half]])

    monkeypatch.setattr(CompileCache, "run", halved)


def _lose_a_request(monkeypatch):
    from repro.serve import frontend
    step = frontend.CNNServingEngine.step

    def losing(self):
        # the first request of the window (warm-up requests have rid -1)
        if (not getattr(self, "_lost", False) and self.pending
                and self.pending[0].rid >= 0):
            self.pending.popleft()
            self._lost = True
        return step(self)

    monkeypatch.setattr(frontend.CNNServingEngine, "step", losing)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_altered_answer_is_caught(tiny_run, monkeypatch, cell):
    _alter_image(monkeypatch)
    r = tiny_run(cell)
    assert not r["correct"], r["checks"]


def test_half_batch_is_caught(tiny_run, monkeypatch):
    _half_batch(monkeypatch)
    r = tiny_run("darknet19.b8")
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_lost_request_is_caught(tiny_run, monkeypatch, cell):
    _lose_a_request(monkeypatch)
    r = tiny_run(cell)
    assert r["checks"]["unanswered"]["value"] == 1, r["checks"]
    assert not r["correct"]
