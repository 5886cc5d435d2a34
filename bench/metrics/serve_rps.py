"""Requests whose score reached the host inside the window, over the
window's seconds."""

import numpy as np


def read(run):
    if run.serve is None:
        return None
    s = run.serve
    done = s["done"]
    n = int(np.sum((done >= s["t0"]) & (done <= s["t1"])))
    return n / run.window_s
