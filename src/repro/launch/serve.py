"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Dispatches on the arch's model *family* instead of assuming every model
speaks the LM prefill/decode interface:

* LM-family archs boot the wave-batched ``ServeEngine`` (prefill +
  KV-cache decode — exactly what ``decode_32k`` lowers in the dry-run);
* ``rec``-family archs (DLRM/DCN) boot the microbatched ``RecsysEngine``
  over post-training-quantized tables (``--quantize {f32,bf16,int8}``)
  with an optional hot-row cache (``--cache-rows N``, device- or
  host-resident via ``--cache-impl``) and continuous or lock-step wave
  batching (``--batching``), optionally sharded across a serving mesh
  (``--mesh-devices N``: plan-aware placement, remote rows over the
  all-to-all exchange), and report table bytes, p50/p99 latency, QPS,
  and cache hit rate.

``--no-reduced`` serves the full-size config (Kaggle cardinalities for the
rec archs).  ``main(argv)`` returns the drained engine, so one process can
train and then serve (a chip belongs to one process at a time).
"""

import argparse
import time

import jax

from .compile_cache import enable_compile_cache
from .mesh import make_mesh


def _serve_lm(mod, args):
    from ..configs.common import Shape
    from ..serve.engine import ServeEngine

    cfg = mod.config(reduced=args.reduced)
    api = mod.api(cfg)
    if api.prefill is None or api.decode is None:
        raise SystemExit(f"{args.arch} has no LM serving path")
    params = api.init(jax.random.PRNGKey(0))

    n_extra = len(api.prefill_inputs(Shape("x", 8, 1, "prefill"))) - 1

    def prefill_fn(tokens, cache):
        if n_extra:  # multimodal stubs: zero frames/patches
            import jax.numpy as jnp
            structs = api.prefill_inputs(Shape("x", tokens.shape[1],
                                               tokens.shape[0], "prefill"))
            extra = tuple(jnp.zeros(s.shape, s.dtype) for s in structs[:-1])
            return api.prefill(params, *extra, tokens, cache)
        return api.prefill(params, tokens, cache)

    engine = ServeEngine(
        prefill_fn=prefill_fn,
        decode_fn=lambda tok, pos, cache: api.decode(params, tok, pos, cache),
        make_cache_fn=api.make_cache,
        batch_size=args.batch_size, max_len=args.max_len,
        temperature=args.temperature)

    for i in range(args.requests):
        plen = 4 if i % 3 else 7
        engine.submit(list(range(1, plen + 1)), max_new_tokens=args.max_new_tokens)
    t0 = time.monotonic()
    done = engine.run_until_drained()
    dt = time.monotonic() - t0
    toks = sum(len(r.output) for r in done.values())
    print(f"{args.arch}: served {len(done)} requests / {toks} tokens in {dt:.2f}s")
    for uid in sorted(done)[:3]:
        print(f"  req {uid}: {done[uid].output}")
    return engine


def synthetic_requests(cfg, n: int, max_bag: int, seed: int = 0):
    """``n`` seeded ``(dense, bags)`` requests: one bag of 1..max_bag ids
    per table, Zipf-skewed towards low ids (the criteo generator's skew)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        dense = rng.normal(size=cfg.dense_dim)
        bags = []
        for s in cfg.table_sizes:
            u = rng.random(int(rng.integers(1, max_bag + 1)))
            bags.append(list((np.floor((u ** 1.5) * s)).astype(np.int64)))
        out.append((dense, bags))
    return out


def _serve_rec(mod, args):
    from ..serve.cache import DeviceHotRowCache, HotRowCache
    from ..serve.quantize import memory_report, quantize_params
    from ..serve.recsys import RecsysEngine
    from .plan_cli import resolve_plan_args

    obs = None
    if args.trace or args.metrics_out or args.replan_interval:
        from ..obs import Obs
        # the replan controller reads collision telemetry, so --replan-
        # interval forces obs on even without --trace/--metrics-out
        obs = Obs(trace=bool(args.trace), collisions=True)

    plan = resolve_plan_args(mod, args)
    cfg = (mod.config(reduced=args.reduced, plan=plan) if plan is not None
           else mod.config(reduced=args.reduced))
    api = mod.api(cfg)
    params = api.init(jax.random.PRNGKey(0))
    qparams = quantize_params(params, mode=args.quantize)
    rep = memory_report(params, qparams)
    print(f"{args.arch}: tables {rep['f32_table_bytes']} B f32 -> "
          f"{rep['quant_table_bytes']} B {args.quantize} "
          f"({rep['ratio']:.3f}x)")

    # cache admits combined f32 rows: 4*D bytes each (quantize.row_bytes
    # is the same accounting the planner's serve-cost model uses).  With
    # only --cache-mb given, rows stay unbounded so the byte budget is
    # the binding limit, not a leftover row default; an explicit
    # --cache-rows 0 disables the cache outright, as documented.
    cache_bytes = (int(args.cache_mb * 2 ** 20)
                   if args.cache_mb is not None else None)
    if args.cache_rows == 0 or (cache_bytes is not None and cache_bytes <= 0):
        cache = None  # explicit zero (rows or bytes) disables the cache
    else:
        cache_rows = (args.cache_rows if args.cache_rows is not None
                      else (None if cache_bytes else 4096))
        cls = (DeviceHotRowCache if args.cache_impl == "device"
               else HotRowCache)
        cache = cls(capacity_rows=cache_rows, capacity_bytes=cache_bytes)
    if args.mesh_devices and args.mesh_devices > 1:
        # sharded serving: plan-aware placement over a 1-D serve mesh —
        # the engine places the tables itself (replicate small, row-shard
        # big) and routes remote rows through the all-to-all exchange
        if args.cache_impl == "host" and cache is not None:
            raise SystemExit("--mesh-devices needs --cache-impl device "
                             "(or --cache-rows 0)")
        # the engine requires max_batch % mesh_devices == 0 (each device
        # takes an equal wave slice); round the CLI default up rather
        # than bounce the user on an internal invariant
        n = args.mesh_devices
        batch = -(-args.batch_size // n) * n
        if batch != args.batch_size:
            print(f"  note: --batch-size {args.batch_size} -> {batch} "
                  f"(must be a multiple of --mesh-devices {n})")
        engine = RecsysEngine(cfg, qparams, max_batch=batch,
                              cache=cache, batching=args.batching,
                              mesh_devices=args.mesh_devices, plan=plan,
                              obs=obs)
        pl = engine.placement
        rep = memory_report(params, qparams, placement=pl)
        print(f"  placement: {len(pl.sharded)} sharded / "
              f"{len(pl.replicated)} replicated sub-tables over "
              f"{pl.n_devices} devices, "
              f"{rep['placement']['table_bytes_per_device']} B/device")
    else:
        mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
        engine = RecsysEngine(cfg, qparams, max_batch=args.batch_size,
                              cache=cache, mesh=mesh,
                              batching=args.batching, obs=obs)

    ctrl = None
    if args.replan_interval:
        from ..online import ReplanController
        from ..plan.planner import full_table_bytes
        if args.mesh_devices and args.mesh_devices > 1:
            raise SystemExit("--replan-interval is single-host "
                             "(swap_plan contract); drop --mesh-devices")
        # re-solve budget: explicit flag > the plan's own budget > the
        # uncompressed f32 footprint (i.e. "no tighter than full tables")
        if args.replan_budget_mb is not None:
            budget = int(args.replan_budget_mb * 2 ** 20)
        elif plan is not None:
            budget = plan.budget_bytes
        else:
            budget = full_table_bytes(cfg.table_sizes, cfg.emb_dim)
        ctrl = ReplanController(engine, budget_bytes=budget,
                                quantize=args.quantize)
        print(f"  replan: every {args.replan_interval} requests, "
              f"budget {budget} B")

    requests = synthetic_requests(cfg, args.requests, args.max_bag)
    done = {}
    interval = args.replan_interval or args.requests
    for start in range(0, args.requests, interval):
        for dense, bags in requests[start:start + interval]:
            engine.submit(dense, bags)
        done.update(engine.run_until_drained())
        if ctrl is not None:
            decision = ctrl.check()
            if decision is not None and decision.fired:
                rep = ctrl.replans[-1]
                print(f"  replan: drift on features {decision.over} -> "
                      f"swapped plan ({rep['plan']['total_bytes']} B, "
                      f"kinds {rep['plan']['kinds']})")
    if ctrl is not None:
        print(f"  replan: {ctrl.checks} windows checked, "
              f"{len(ctrl.replans)} plan swaps")
    m = engine.metrics()
    print(f"{args.arch}: served {len(done)} requests in {m['waves']} waves | "
          f"p50 {m['p50_ms']:.1f} ms  p99 {m['p99_ms']:.1f} ms  "
          f"qps {m['qps']:.1f}")
    print(f"  wave paths: {m['paths']}")
    if cache is not None:
        print(f"  cache: hit_rate {m['cache']['hit_rate']:.3f} "
              f"({m['cache']['hits']}/{m['cache']['lookups']}), "
              f"{m['cache']['bytes_cached']} B resident")
    if obs is not None:
        ss = engine.stage_summary()
        parts = "  ".join(
            f"{s} {ss[s]['sum'] * 1e3 / max(1, ss[s]['count']):.2f}ms"
            for s in ("probe", "dense", "inflight", "miss_gather", "flush"))
        print(f"  stages (mean/wave): {parts}")
        obs.save(metrics_path=args.metrics_out, trace_path=args.trace)
        for p in (args.metrics_out, args.trace):
            if p:
                print(f"  obs: wrote {p}")
    for uid in sorted(done)[:3]:
        print(f"  req {uid}: score {done[uid].score:+.4f}")
    return engine


def main(argv=None):
    """Parse ``argv`` (default ``sys.argv[1:]``), serve, return the engine."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--no-reduced", dest="reduced", action="store_false")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--batch-size", type=int, default=4)
    # LM knobs
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--max-new-tokens", type=int, default=12)
    ap.add_argument("--temperature", type=float, default=0.0)
    # recsys knobs
    ap.add_argument("--quantize", default="int8", choices=["f32", "bf16", "int8"])
    ap.add_argument("--cache-rows", type=int, default=None,
                    help="hot-row cache row capacity (0 disables the cache "
                         "entirely; default 4096, or unbounded rows when "
                         "--cache-mb alone is given so the byte budget "
                         "actually binds)")
    ap.add_argument("--cache-mb", type=float, default=None,
                    help="hot-row cache byte budget (admission stops at "
                         "this many MiB of resident f32 rows)")
    ap.add_argument("--cache-impl", default="device",
                    choices=["device", "host"],
                    help="hot-row cache storage: 'device' keeps rows in "
                         "HBM slabs with an in-graph slot-map probe (the "
                         "fast path), 'host' is the PR 3 host-dict cache")
    ap.add_argument("--batching", default="continuous",
                    choices=["continuous", "waves"],
                    help="'continuous' pipelines waves (dispatch ahead "
                         "while earlier waves settle), 'waves' is the "
                         "lock-step pow2 scheduler")
    ap.add_argument("--max-bag", type=int, default=4,
                    help="max multi-hot ids per categorical feature")
    ap.add_argument("--mesh-devices", type=int, default=None,
                    help="serve the tables sharded across this many "
                         "devices (plan-aware placement: replicate small "
                         "sub-tables, row-shard big ones; batch size must "
                         "be a multiple of it)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of per-wave "
                         "stage timelines to PATH (rec family; implies "
                         "obs on)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics registry as JSONL to PATH "
                         "(rec family; implies obs on)")
    ap.add_argument("--replan-interval", type=int, default=None,
                    help="run the online drift controller: drain and run "
                         "one detector check every N requests, re-solving "
                         "and hot-swapping the plan when drift persists "
                         "(rec family, single-host; implies obs on; off "
                         "by default)")
    ap.add_argument("--replan-budget-mb", type=float, default=None,
                    help="byte budget for online re-solves in MiB "
                         "(default: the current plan's budget, or the "
                         "f32 table footprint when serving unplanned)")
    from .plan_cli import add_plan_args
    add_plan_args(ap)
    args = ap.parse_args(argv)

    enable_compile_cache()
    from ..configs import get_arch
    mod = get_arch(args.arch)
    if getattr(mod, "FAMILY", "lm") == "rec":
        return _serve_rec(mod, args)
    return _serve_lm(mod, args)


if __name__ == "__main__":
    main()
