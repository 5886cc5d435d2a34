"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Reduced configs by default; ``--no-reduced`` trains the full-size config
(Kaggle cardinalities for the rec archs) through the same code path —
jitted step, sharded loader, async checkpoints, restart-safe.
``main(argv)`` returns the
final state, the logged ``(step, loss)`` history and the per-step wall
times, so one process can train and then serve.
"""

import argparse

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .compile_cache import enable_compile_cache
from .mesh import make_mesh
from .plan_cli import add_plan_args, resolve_plan_args


def main(argv=None):
    """Parse ``argv`` (default ``sys.argv[1:]``) and train."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dlrm-criteo")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--embedding", default="qr")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--no-reduced", dest="reduced", action="store_false")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--compress-policy", default=None,
                    choices=["auto", "none", "bf16", "int8"],
                    help="gradient-compression policy for the explicit "
                         "data-parallel step (repro.dist.policy); omit for "
                         "the plain pjit step")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of per-step "
                         "spans to PATH (implies obs on)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics registry as JSONL to PATH "
                         "(implies obs on)")
    add_plan_args(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()

    from ..configs import get_arch
    from ..configs.common import Shape
    from ..train.loop import (TrainConfig, Trainer, init_dp_state, init_state,
                              make_dp_train_step, make_train_step)

    mod = get_arch(args.arch)
    plan = resolve_plan_args(mod, args)
    if plan is not None:
        cfg = mod.config(reduced=args.reduced, plan=plan)
    else:
        cfg = mod.config(reduced=args.reduced, embedding=args.embedding)
    api = mod.api(cfg)
    shape = Shape("cli", args.seq_len, args.batch, "train")

    params = api.init(jax.random.PRNGKey(0))
    n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    emb_desc = "plan" if plan is not None else args.embedding
    print(f"{args.arch}: {n:,} parameters (embedding={emb_desc})")

    if args.compress_policy is not None:
        # ROADMAP follow-up: the policy engine, selectable from the CLI.
        # Explicit shard_map DP step over every local device; "auto" is the
        # per-leaf rule table (int8 tables / bf16 dense / none small).
        n_dev = jax.device_count()
        if args.batch % n_dev:
            raise SystemExit(f"--batch {args.batch} must be a multiple of "
                             f"the device count {n_dev} for the dp step")
        mesh = make_mesh((n_dev,), ("data",))
        # replicated on the mesh from the start, as every step returns it:
        # a state on one device would recompile the step at step 1
        state = jax.device_put(
            init_dp_state(params, api.optimizer,
                          compress=args.compress_policy),
            NamedSharding(mesh, PartitionSpec()))
        step = make_dp_train_step(api.loss_fn, api.optimizer, mesh,
                                  compress=args.compress_policy)
        print(f"dp step over {n_dev} device(s), "
              f"compress={args.compress_policy}")
    else:
        state = init_state(params, api.optimizer)
        step = make_train_step(api.loss_fn, api.optimizer)
    obs = step_wire = None
    if args.trace or args.metrics_out:
        from ..obs import Obs
        obs = Obs(trace=bool(args.trace))
        if args.compress_policy is not None:
            # accounted per-leaf wire bytes of one dp step -> counters
            from ..dist.accounting import grad_wire_bytes
            step_wire = grad_wire_bytes(params, args.compress_policy,
                                        jax.device_count())
    tc = TrainConfig(num_steps=args.steps, log_every=args.log_every,
                     ckpt_every=max(50, args.steps // 4), ckpt_dir=args.ckpt_dir)
    trainer = Trainer(step, tc, batch_at=lambda s: api.batch_fn(s, shape),
                      obs=obs, step_wire=step_wire)
    state = trainer.resume_or(state)
    state, history = trainer.run(state)
    for step, loss in history:
        print(f"step {step:5d}  loss {loss:.4f}")
    if trainer.straggler_events:
        print("straggler events:", trainer.straggler_events)
    if obs is not None:
        obs.save(metrics_path=args.metrics_out, trace_path=args.trace)
        for p in (args.metrics_out, args.trace):
            if p:
                print(f"obs: wrote {p}")
    return {"state": state, "history": history,
            "step_seconds": trainer.step_seconds}


if __name__ == "__main__":
    main()
