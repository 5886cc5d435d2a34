"""A run whose timed path is broken underneath reports ``correct`` false:
the harness's look for a chip is skipped, everything else runs as on the
chip, at reduced sizes on the CPU."""

import pytest

import bench_fixture as bf
from bench import harness

SEED = 2 ** 31 + 9


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bf.reduced_copy(tmp_path_factory.mktemp("bench"))


def _altered_answers(monkeypatch):
    from repro.serve.recsys import RecsysEngine
    reap = RecsysEngine._reap

    def broken(self):
        wave = reap(self)
        wave[0].score += 0.5       # one answer altered where it is produced
        return wave
    monkeypatch.setattr(RecsysEngine, "_reap", broken)


def _train_fault(kind):
    def patch(monkeypatch):
        from repro.train import loop
        make = loop.make_train_step

        def broken(loss_fn, optimizer, **kw):
            step = make(loss_fn, optimizer, **kw)

            def run(state, batch):
                if kind == "half_batch":
                    half = batch["label"].shape[0] // 2
                    return step(state, {k: v[:half] for k, v in batch.items()})
                new, met = step(state, batch)
                return dict(state, step=new["step"]), met   # state unchanged
            return run
        monkeypatch.setattr(loop, "make_train_step", broken)
    return patch


CASES = [
    ("dlrm-criteo-kaggle.serve-multihot-tail", _altered_answers),
    ("dcn-criteo-kaggle.serve-onehot-overload", _altered_answers),
] + [(cell, _train_fault(kind))
     for cell in ("dlrm-criteo-kaggle.train-b2048",
                  "dcn-criteo-kaggle.train-b2048")
     for kind in ("unchanged_state", "half_batch")]


@pytest.mark.parametrize("cell,fault", CASES,
                         ids=[f"{c}-{i}" for i, (c, _) in enumerate(CASES)])
def test_broken_timed_path_is_not_correct(root, cell, fault, monkeypatch):
    fault(monkeypatch)
    _, line = harness.execute(cell, SEED, 0.5, False, root=root,
                              require_chip=False)
    assert line["correct"] is False, line["checks"]
