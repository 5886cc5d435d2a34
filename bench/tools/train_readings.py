#!/usr/bin/env python3
"""Per-leaf readings of a training cell's first three steps, for many seeds
in one process: the program (the harness's ``Trainer`` step), the plain
reference, the bfloat16 control and the half-batch fault, so the numbers
``correct`` compares can be chosen and their limits set.

    python3 bench/tools/train_readings.py --workload <cell> --seeds 1,2,3

One JSON line per seed: each side's losses, and per leaf the norm of the
first gradient and of the parameters' change after one and after three
steps.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


from bench import harness, spec  # noqa: E402
from bench.reference import api as ref  # noqa: E402
from bench.reference.common import first_grad_norms, leaf_norms  # noqa: E402


def program_side(cfg, make_batch, seed, step):
    """``step`` is the program's step jitted as ``Trainer`` jits it."""
    import jax
    from repro.train.loop import init_state
    api, opt = cfg["program_api"], cfg["train"]
    params0 = ref.make_params(seed, cfg["model"])
    state = init_state(params0, api.optimizer)
    losses, change = [], []
    for t in range(3):
        state, met = step(state, make_batch(t))
        losses.append(float(met["loss"]))
        if t == 0:
            first = first_grad_norms(opt, state["opt"])
        if t in (0, 2):
            change.append(leaf_norms(jax.tree.map(lambda a, b: a - b,
                                                  state["params"], params0)))
    return {"losses": losses, "first_grad": first.tolist(),
            "change1": change[0].tolist(), "change": change[1].tolist()}


def ref_side(cfg, batches, seed, dtype):
    model, opt = cfg["model"], cfg["train"]
    three = ref.train_steps(ref.make_params(seed, model), batches, model, opt,
                            dtype=dtype)
    one = ref.train_steps(ref.make_params(seed, model), batches[:1], model, opt,
                          dtype=dtype)
    return {"losses": three["losses"], "first_grad": three["first_grad"].tolist(),
            "change1": one["change"].tolist(), "change": three["change"].tolist()}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    from repro.train.loop import make_train_step
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    harness.check_devices(cell["chips"])
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = spec.load_config(bench, cell["config"])
    mix = spec.load_mix(cell["traffic"])
    cfg["program_cfg"], cfg["program_api"] = harness.program_config(cfg)
    api = cfg["program_api"]
    step = jax.jit(make_train_step(api.loss_fn, api.optimizer))

    for seed in (int(s) for s in args.seeds.split(",")):
        make_batch = spec.process(mix["process"]).batch_fn(mix, cfg["model"], seed)
        batches = [make_batch(t) for t in range(3)]
        half = [{k: v[: v.shape[0] // 2] for k, v in b.items()} for b in batches]
        out = {"seed": seed,
               "program": program_side(cfg, make_batch, seed, step),
               "reference": ref_side(cfg, batches, seed, jnp.float32),
               "control": ref_side(cfg, batches, seed, jnp.bfloat16),
               "half_batch": ref_side(cfg, half, seed, jnp.float32)}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
