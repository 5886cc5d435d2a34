"""How late the load generator submitted: 99th percentile over the
window's requests of submit time minus due time (the host's clock)."""

import numpy as np


def read(run):
    if run.serve is None or not len(run.serve["due"]):
        return None
    lag = run.serve["submitted"] - run.serve["due"]
    return float(np.percentile(lag[np.isfinite(lag)] * 1e3, 99, method="higher"))
