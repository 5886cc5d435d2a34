"""The train step's share of its HBM roofline in the traced window: the
least bytes of the steps that ran in it over the device time of the step
program, per chip, over the chip's HBM bandwidth, in percent.  The least
bytes (``_counts``): the rows that live bag slots of the step's whole
batch touch and the dense parameters, with their optimizer state and
gradients (``train_step_min_bytes``: a batch's ``sparse`` in any of its
forms, a row-wise optimizer's state by the row); a data-parallel step with an
exchange under error feedback adds its residual state
(``error_state_min_bytes``), as every replica keeps all of it."""

import numpy as np

from bench.metrics._counts import error_state_min_bytes, train_step_min_bytes


def read(run):
    if run.trace is None or run.train is None or "traced_steps" not in run.train:
        return None
    module = run.train["step_module"]
    secs = run.trace.module_s.get(module, 0.0)
    calls = run.trace.module_calls.get(module, 0)
    steps = run.train["traced_steps"]
    if secs <= 0 or not calls or not steps:
        return None
    model, train = run.cfg["model"], run.cfg["train"]
    batches = (run.train["batch_at"](s) for s in steps)
    per_step = np.mean([train_step_min_bytes(model, train, b["sparse"],
                                             b.get("mask")) for b in batches])
    if train.get("compress") is not None:
        per_step += error_state_min_bytes(model)
    return 100.0 * per_step * calls / secs / run.peaks["hbm_bytes_per_s"]
