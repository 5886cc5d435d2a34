#!/usr/bin/env python3
"""Per-leaf readings of a training cell's first three steps, for many seeds
in one process: the program (the harness's own ``Trainer`` step, on the
cell's chips), the plain reference, the bfloat16 control and the
half-batch fault; on several chips also the exchange left out (each
replica steps on its own shard of the batch), and for an int8 exchange
the reference's exchange in int4, so the numbers ``correct`` compares
can be chosen and their limits set.

    python3 bench/tools/train_readings.py --workload <cell> --seeds 1,2,3

One JSON line per seed: each side's losses, and per leaf the norm of the
first gradient and of the parameters' change after one and after three
steps; with ``numbers`` each side's numbers as ``correct`` reads them.
A cell's batches may take any form the harness takes (the list form of
fixed-width bags among them); the half batch halves each array of it.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


from bench import faults, harness, spec  # noqa: E402
from bench.reference import api as ref  # noqa: E402
from bench.reference.common import first_grad_norms, leaf_norms  # noqa: E402


def program_side(run, trainer, start, weights):
    """Three steps of ``trainer`` from the seed's ``weights`` (left
    readable), read as the harness reads them."""
    import jax
    opt = run.cfg["train"]
    state, params0 = start(weights)
    losses, change = [], []
    for t in range(3):
        state, met = trainer.train_step(state, trainer.batch_at(t))
        losses.append(float(met["loss"]))
        if t == 0:
            first = first_grad_norms(opt, state["opt"], state["params"])
        if t in (0, 2):
            change.append(leaf_norms(jax.tree.map(lambda a, b: a - b,
                                                  state["params"], params0)))
    return {"losses": losses, "first_grad": first.tolist(),
            "change1": change[0].tolist(), "change": change[1].tolist()}


def ref_side(cfg, batches, weights, replicas, dtype, bits=None):
    model, opt = cfg["model"], cfg["train"]
    three = ref.train_steps(weights, batches, model, opt,
                            dtype=dtype, replicas=replicas, bits=bits)
    return {"losses": three["losses"], "first_grad": three["first_grad"].tolist(),
            "change1": three["change1"].tolist(),
            "change": three["change"].tolist()}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls-only", action="store_true",
                    help="only the reference and the controls")
    ap.add_argument("--fault-seeds", type=int, default=None, metavar="N",
                    help="the faults and the int4 exchange on the first N "
                         "seeds only (default: every seed)")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.compile_cache import enable_compile_cache
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    devices = harness.check_devices(cell["chips"])
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = spec.load_config(bench, cell["config"])
    mix = spec.load_mix(cell["traffic"])
    cfg["program_cfg"], cfg["program_api"] = harness.program_config(cfg)
    run = harness.Run(cell, cfg, mix, {}, devices)
    proc = spec.process(mix["process"])

    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        more = (not args.controls_only
                and (args.fault_seeds is None or i < args.fault_seeds))
        make_batch = proc.batch_fn(mix, cfg["model"], seed)
        trainer, start = harness.train_program(run, make_batch)
        weights = ref.make_params(seed, cfg["model"])
        out = {"seed": seed}
        if not args.controls_only:
            out["program"] = program_side(run, trainer, start, weights)
        batch_at = trainer.batch_at
        del trainer
        if cell["chips"] > 1 and more:
            with faults.plant("no_exchange"):
                trainer, start = harness.train_program(run, make_batch)
                out["no_exchange"] = program_side(run, trainer, start, weights)
            del trainer
        # the batches the program stepped on, whole, on the reference's device
        batches = [jax.device_get(batch_at(t)) for t in range(3)]
        half = [jax.tree.map(lambda v: v[: v.shape[0] // 2], b) for b in batches]
        n = cell["chips"]
        out.update(reference=ref_side(cfg, batches, weights, n, jnp.float32),
                   control=ref_side(cfg, batches, weights, n, jnp.bfloat16))
        if more:
            out["half_batch"] = ref_side(cfg, half, weights, n, jnp.float32)
        if cfg["train"].get("compress") == "int8" and (more or args.controls_only):
            out["int4_exchange"] = ref_side(cfg, batches, weights, n,
                                            jnp.float32, bits=4)
        want = {k: np.asarray(v) for k, v in out["reference"].items()}
        out["numbers"] = {
            side: harness.train_numbers(
                {k: np.asarray(v) for k, v in out[side].items()}, want)
            for side in out if side not in ("seed", "reference")}
        out["seconds"] = time.monotonic() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
