"""Open-loop Poisson arrivals: a fixed number of requests, ``rate_rps`` times
the span, at times drawn uniformly over the span and sorted (a Poisson
process given its count, so every seed offers the same load), each drawn
by ``generator.draw_requests``.  One client submits each request when it
is due, whatever is still outstanding."""

from __future__ import annotations

import numpy as np

from bench import generator
from bench.serve import OpenLoop

ENTRY = "serve"
Loop = OpenLoop


def requests(mix: dict, model: dict, seed: int, stream: int, seconds: float,
             rate: float | None = None) -> generator.Requests:
    """The requests due in a span of ``seconds`` at ``rate`` (default: the
    mix's ``rate_rps``), from stream ``stream`` of ``seed``."""
    rng = generator.rng_for(seed, stream)
    n = max(1, int(round((rate or mix["rate_rps"]) * seconds)))
    due = np.sort(rng.uniform(0.0, seconds, n))
    return generator.draw_requests(mix, model, rng, due)
