"""Device milliseconds a traced step spends in collective ops, per chip
(``tracing.COLLECTIVES``: all-to-all, all-gather, all-reduce,
reduce-scatter, collective-permute and their async halves): the wire part
of a data-parallel step's gradient exchange.  The exchange's local passes
(quantise, error feedback, relayouts) are not collectives and are not
counted here."""

from bench import tracing


def read(run):
    if (run.trace is None or run.train is None or run.cell["chips"] < 2
            or "traced_steps" not in run.train):
        return None
    secs = sum(tracing.collective_ops(run.trace).values())
    steps = len(run.train["traced_steps"])
    if secs <= 0 or not steps:
        return None
    return 1e3 * secs / steps
