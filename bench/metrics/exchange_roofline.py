"""The gradient exchange's share of its interconnect roofline in the
traced window: the least bytes a chip must send in the steps that ran in
it (``_counts.exchange_min_bytes``: ``2(n-1)/n`` of the compressed
gradient payload) over the device time of the collective ops, over the
chip's inter-chip bandwidth, in percent."""

from bench import tracing
from bench.metrics._counts import exchange_min_bytes


def read(run):
    if (run.trace is None or run.train is None or run.cell["chips"] < 2
            or "traced_steps" not in run.train):
        return None
    secs = sum(tracing.collective_ops(run.trace).values())
    steps = len(run.train["traced_steps"])
    compress = run.cfg["train"].get("compress")
    if secs <= 0 or not steps or compress is None:
        return None
    least = exchange_min_bytes(run.cfg["model"], compress, run.cell["chips"])
    return 100.0 * least * steps / secs / run.peaks["ici_bytes_per_s"]
