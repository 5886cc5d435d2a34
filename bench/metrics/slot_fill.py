"""Live bag slots over the padded ``(Bb, F, Lb)`` slots of the waves served
in the window (traced run), in percent: what batching and pow2 padding
leave of the embed's work."""


def read(run):
    if run.serve is None or not run.serve["waves"]:
        return None
    f = len(run.cfg["model"]["table_sizes"])
    live = sum(w["requests"] for w in run.serve["waves"]) * run.serve["live_slots"]
    padded = sum(w["batch"] * f * w["bag"] for w in run.serve["waves"])
    return 100.0 * live / padded
