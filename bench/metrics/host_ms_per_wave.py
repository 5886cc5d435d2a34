"""Host work per served wave, from the engine's stage timers (traced run,
window only): padding, the cache probe or lookups, the dense dispatch and
settling misses (``pad + probe + dense + miss_gather``), mean per wave."""

HOST_STAGES = ("pad", "probe", "dense", "miss_gather")


def read(run):
    if run.serve is None or not run.serve["stages"]:
        return None
    st = run.serve["stages"]
    waves = st["pad"][1]
    if not waves:
        return None
    return 1e3 * sum(st[s][0] for s in HOST_STAGES) / waves
