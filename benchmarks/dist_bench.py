"""Wire-bytes vs step-time benchmark for the compression policy engine.

Runs the paper's DLRM (reduced Criteo config) on the 8-forced-host-device
mesh under each compression policy, through both distributed grad paths:

* ``dp``   — ``make_dp_train_step`` (replicated params, compressed
  all-reduce);
* ``fsdp`` — ``make_fsdp_train_step`` (reduce-scatter grads, sharded opt
  state, param all-gather).

Per (path × policy) row it reports the **accounted** per-chip collective
wire bytes (``repro.dist.accounting``), the **HLO cross-check** (the same
ring formulas applied to the compiled step by ``launch.hlo_analysis`` —
what XLA actually put on the wire), measured step time, and the loss
after ``--steps`` training steps (compression must not wreck
convergence, or the wire saving is fiction).

Artifacts: ``artifacts/bench/BENCH_dist.json`` + CSV on stdout
(``name,us_per_call,derived``).  Exits non-zero — with ``/ERROR`` rows —
if any section raises, if accounting and HLO disagree by more than 10%,
or if the int8 policy fails to cut DP wire bytes below 0.3× of
``mode="none"`` (the acceptance bar: 1 B/elem both phases vs 4 B/elem ⇒
~0.25× + scale scalars).

Usage::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m benchmarks.dist_bench --steps 30
"""

from __future__ import annotations

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import sys
import time

ART = "artifacts/bench"
HLO_MATCH_TOL = 0.10
INT8_RATIO_BAR = 0.30
# loss/bce/acc pmeans in the step (loss and bce CSE into one all-reduce is
# sub-1e-5 of the total; we count all three)
SCALAR_ALLREDUCES = 3


def _build():
    import jax

    from repro.configs import dlrm_criteo
    from repro.data.criteo import CriteoSpec, batch_at

    cfg = dlrm_criteo.config(reduced=True)
    api = dlrm_criteo.api(cfg)
    spec = CriteoSpec(table_sizes=cfg.table_sizes, zipf=1.5, noise=0.5)
    params = api.init(jax.random.PRNGKey(0))
    batcher = lambda i: batch_at(0, i, 256, spec)
    return api, params, batcher


def _measure(step, state, batcher, steps, warmup=2):
    import jax
    state, m = step(state, batcher(0))  # compile + first step
    jax.block_until_ready(m["loss"])
    t0 = time.monotonic()
    timed = 0
    for i in range(1, steps):
        state, m = step(state, batcher(i))
        if i == warmup:
            jax.block_until_ready(m["loss"])
            t0 = time.monotonic()
        timed = i - warmup
    jax.block_until_ready(m["loss"])
    us = (time.monotonic() - t0) / max(timed, 1) * 1e6
    return float(m["loss"]), us


def bench(steps: int, policies: list[str], paths: list[str]) -> dict:
    import jax

    from repro.dist import AUTO, accounting
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_mesh
    from repro.train.loop import (init_dp_state, init_fsdp_state,
                                  make_dp_train_step, make_fsdp_train_step)

    api, params, batcher = _build()
    n = jax.device_count()
    mesh = make_mesh((n,), ("data",))
    rows = []
    for path in paths:
        for name in policies:
            pol = AUTO if name == "auto" else name
            t0 = time.monotonic()
            if path == "dp":
                state = init_dp_state(params, api.optimizer, compress=pol)
                step = make_dp_train_step(api.loss_fn, api.optimizer, mesh,
                                          compress=pol)
                acct = accounting.dp_step_wire_bytes(
                    params, pol, n, scalar_allreduces=SCALAR_ALLREDUCES)
            else:
                state = init_fsdp_state(params, api.optimizer, mesh, policy=pol)
                step = make_fsdp_train_step(api.loss_fn, api.optimizer, mesh,
                                            params, policy=pol)
                acct = accounting.fsdp_step_wire_bytes(
                    params, api.optimizer, mesh, pol,
                    scalar_allreduces=SCALAR_ALLREDUCES)
            # one wrapper per lane: lower/compile and the timed run share
            # the same jit cache, so _measure never recompiles the step
            jitted = jax.jit(step)  # repro: noqa[JIT-001] step is a fresh closure per (path, policy) lane — one wrapper per lane is the minimum
            with jax.set_mesh(mesh):
                compiled = jitted.lower(state, batcher(0)).compile()
                compile_s = time.monotonic() - t0
                cost = analyze_hlo(compiled.as_text(), total_devices=n)
                loss, us = _measure(jitted, state, batcher, steps)
            rows.append({
                "path": path, "policy": name, "devices": n,
                "wire_bytes": acct["total_bytes"],
                "wire_bytes_grads": acct["grad_bytes"],
                "wire_bytes_param_gather": acct["param_gather_bytes"],
                "hlo_wire_bytes": cost.collective_bytes,
                "hlo_collectives": cost.collectives,
                "step_time_us": round(us, 1),
                "loss_after_steps": loss, "train_steps": steps,
                "compile_s": round(compile_s, 2),
            })
    return {"arch": "dlrm-criteo(reduced)", "batch": 256, "devices": n,
            "rows": rows}


def check(report: dict) -> list[tuple[str, str]]:
    """(name, message) per failed acceptance check; empty = all green."""
    failures = []
    by = {(r["path"], r["policy"]): r for r in report["rows"]}
    for r in report["rows"]:
        hlo = r["hlo_wire_bytes"]
        if hlo <= 0:
            failures.append((f"{r['path']}/{r['policy']}",
                             "no collectives found in compiled HLO"))
            continue
        rel = abs(r["wire_bytes"] - hlo) / hlo
        if rel > HLO_MATCH_TOL:
            failures.append(
                (f"{r['path']}/{r['policy']}",
                 f"accounting {r['wire_bytes']:.0f} vs HLO {hlo:.0f} "
                 f"differs {rel:.1%} > {HLO_MATCH_TOL:.0%}"))
    if ("dp", "int8") in by and ("dp", "none") in by:
        ratio = by[("dp", "int8")]["hlo_wire_bytes"] \
            / by[("dp", "none")]["hlo_wire_bytes"]
        report["int8_vs_none_ratio"] = ratio
        if ratio >= INT8_RATIO_BAR:
            failures.append(("dp/int8",
                             f"wire ratio {ratio:.3f} >= {INT8_RATIO_BAR}"))
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30,
                    help="training steps per (path, policy) cell")
    ap.add_argument("--policies", default="none,bf16,int8,auto")
    ap.add_argument("--paths", default="dp,fsdp")
    ap.add_argument("--out", default=os.path.join(ART, "BENCH_dist.json"))
    args = ap.parse_args(argv)

    print("name,us_per_call,derived")
    try:
        report = bench(args.steps, args.policies.split(","),
                       args.paths.split(","))
    except Exception as e:
        print(f"dist_bench/ERROR,0,{repr(e)[:160]}")
        return 1
    for r in report["rows"]:
        print(f"dist/{r['path']}/{r['policy']},{r['step_time_us']},"
              f"wire_bytes={r['wire_bytes']:.0f};hlo={r['hlo_wire_bytes']:.0f};"
              f"loss={r['loss_after_steps']:.4f}")
    failures = check(report)
    report["checks_failed"] = [f"{n}: {m}" for n, m in failures]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, default=float)
    for name, msg in failures:
        print(f"dist/check/{name}/ERROR,0,{msg}")
    if failures:
        print(f"# {len(failures)} dist_bench check(s) failed", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
