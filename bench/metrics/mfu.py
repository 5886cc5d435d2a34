"""Model FLOPs of what the window completed (forward per scored request;
forward and backward, 3x, per trained example) per second, over the bf16
peak of the cell's chips, in percent.  Counted from the configuration's
shapes."""

import numpy as np

from bench.metrics._counts import forward_flops


def read(run):
    model = run.cfg["model"]
    peak = run.cell["chips"] * run.peaks["bf16_flops_per_s"]
    if run.train is not None:
        rate, per = run.train["examples"] / run.window_s, 3 * forward_flops(model)
    elif run.serve is not None:
        s = run.serve
        n = int(np.sum((s["done"] >= s["t0"]) & (s["done"] <= s["t1"])))
        rate, per = n / run.window_s, forward_flops(model)
    else:
        return None
    return 100.0 * rate * per / peak
