"""Median latency of every request due in the window, from when it was due
to when its score was on the host: a request the window left unsubmitted
or unanswered counts with its wait after the close, and one never answered
counts as infinitely late."""

import numpy as np


def read(run):
    if run.serve is None or not len(run.serve["due"]):
        return None
    s = run.serve
    done = np.where(np.isnan(s["done"]), np.inf, s["done"])
    return float(np.percentile((done - s["due"]) * 1e3, 50, method="higher"))
