"""The train step's share of its HBM roofline in the traced window: the
least bytes of the steps that ran in it (``_counts.train_step_min_bytes``:
touched rows and dense parameters with their optimizer state and
gradients) over the device time of the step program, over the chip's HBM
bandwidth, in percent."""

import numpy as np

from bench.metrics._counts import train_step_min_bytes

STEP_MODULE = "jit_step"


def read(run):
    if run.trace is None or run.train is None or "traced_steps" not in run.train:
        return None
    secs = run.trace.module_s.get(STEP_MODULE, 0.0)
    calls = run.trace.module_calls.get(STEP_MODULE, 0)
    steps = run.train["traced_steps"]
    if secs <= 0 or not calls or not steps:
        return None
    model, opt = run.cfg["model"], run.cfg["train"]["name"]
    per_step = np.mean([
        train_step_min_bytes(model, opt,
                             np.asarray(run.train["batch_at"](s)["sparse"]))
        for s in steps])
    return 100.0 * per_step * calls / secs / run.peaks["hbm_bytes_per_s"]
