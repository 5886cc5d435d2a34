"""Faults planted in a training cell's timed path, for the tests and the
tools that show ``correct`` comes out false when the step goes wrong:

* ``unchanged_state``: the step returns its state unchanged (only the
  step counter moves);
* ``half_batch``: the step sees half of each batch, the mean taken over it;
* ``no_exchange``: the data-parallel step leaves out the gradient
  exchange, so each replica applies its own shard's gradient.

``plant(fault)`` patches ``repro.train.loop`` for the steps built inside
it (``harness.train_program`` builds its step there)."""

from __future__ import annotations

import contextlib

FAULTS = ("unchanged_state", "half_batch", "no_exchange")


@contextlib.contextmanager
def plant(fault: str):
    import jax
    from repro.train import loop
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    if fault == "no_exchange":
        name, keep = "ef_psum_grads", loop.ef_psum_grads
        loop.ef_psum_grads = lambda grads, err, **kw: (grads, err)
    else:
        name, keep = "make_dp_train_step", loop.make_dp_train_step

        def broken(*a, **kw):
            step = keep(*a, **kw)

            def run(state, batch):
                if fault == "half_batch":
                    half = batch["label"].shape[0] // 2
                    return step(state, jax.tree.map(lambda v: v[:half], batch))
                new, met = step(state, batch)
                return dict(state, step=new["step"]), met
            return run
        loop.make_dp_train_step = broken
    try:
        yield
    finally:
        setattr(loop, name, keep)
