"""The embed's share of its HBM roofline in the traced window: the least
bytes the waves reaped in the window had to move (``_counts.
embed_min_bytes``) over the device time of every program but the dense
stage (the embed programs, the cache's gathers and scatters, the miss
rows), over the chip's HBM bandwidth, in percent."""

from bench.metrics._counts import embed_min_bytes
from bench.generator import bag_lengths

DENSE_MODULE = "lambda"     # the engine's dense stage is a jitted lambda


def read(run):
    if run.trace is None or run.serve is None or "traced" not in run.serve:
        return None
    tr = run.serve["traced"]
    model = run.cfg["model"]
    lens = bag_lengths(run.mix, model)
    row, total = tr["first_row"], 0
    for w in tr["waves"]:
        total += embed_min_bytes(model, tr["reqs"].ids[row:row + w["requests"]],
                                 lens)
        row += w["requests"]
    secs = sum(v for k, v in run.trace.module_s.items() if DENSE_MODULE not in k)
    if not total or secs <= 0:
        return None
    return 100.0 * total / secs / run.peaks["hbm_bytes_per_s"]
