#!/usr/bin/env python3
"""Smoke run of DLRM-Criteo at full Kaggle size on TPU chips.

    python chip_smoke.py             # one chip: train, serve, kernel phases
    python chip_smoke.py --chips 4   # four chips: sharded serving, int8 DP

The paper's model (``dlrm-criteo``: Kaggle cardinalities, D=16, QR
embeddings) runs through the entry points a user calls —
``repro.launch.train.main``, ``repro.launch.serve.main`` and
``RecsysEngine`` — all in this one process: a chip belongs to one process
at a time, so nothing here starts a child that touches JAX.  Weights come
from the launchers' fixed seed and requests from their seeded generator.

Each phase prints one line with its timings; compilation is counted in
``setup_s``.  These are single runs of a smoke test, not benchmark numbers.
A failed check raises, so the process exits non-zero and never prints the
last line, which on success is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

with N the number of chips used.  Without a TPU the run stops before any
phase.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "dlrm-criteo"
REDUCED = False          # full Kaggle cardinalities (tests flip it on CPU)
TRAIN_STEPS, TRAIN_BATCH = 4, 256
REQUESTS, SERVE_BATCH, MAX_BAG = 48, 16, 4
SHARDED_CHIPS = 4
# Largest |logit| difference accepted between two serving paths of the same
# int8 params.  Rounding the interaction inputs to bf16 (the TPU's default
# f32 matmul pass) moves logits of this model by at most 6.7e-4 (CPU
# estimate); a wrong embedding row moves them by about 1e-2 or more.
SCORE_ATOL = 5e-3


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def size_flag() -> str:
    return "--reduced" if REDUCED else "--no-reduced"


def train_args() -> list[str]:
    return ["--arch", ARCH, size_flag(), "--steps", str(TRAIN_STEPS),
            "--batch", str(TRAIN_BATCH), "--log-every", "1"]


def requests():
    from repro.configs import get_arch
    from repro.launch.serve import synthetic_requests
    cfg = get_arch(ARCH).config(reduced=REDUCED)
    return synthetic_requests(cfg, REQUESTS, MAX_BAG)


def padded(reqs, n_features):
    """``(dense, idx, mask)`` arrays of a request list, bags padded to the
    longest with masked slots."""
    lmax = max(len(b) for _, bags in reqs for b in bags)
    dense = np.stack([np.asarray(d, np.float32) for d, _ in reqs])
    idx = np.zeros((len(reqs), n_features, lmax), np.int32)
    mask = np.zeros((len(reqs), n_features, lmax), np.float32)
    for r, (_, bags) in enumerate(reqs):
        for i, bag in enumerate(bags):
            idx[r, i, :len(bag)] = bag
            mask[r, i, :len(bag)] = 1.0
    return dense, idx, mask


def serve_scores(engine, reqs) -> np.ndarray:
    uids = [engine.submit(d, b) for d, b in reqs]
    done = engine.run_until_drained()
    return np.asarray([done[u].score for u in uids], np.float32)


def train_phase(argv):
    from repro.launch import train
    t0 = time.monotonic()
    out = train.main(argv)
    wall = time.monotonic() - t0
    losses = [loss for _, loss in out["history"]]
    check(len(losses) == len(out["step_seconds"]) > 1
          and all(math.isfinite(x) for x in losses),
          f"train: losses {losses}")
    params = jax.tree.leaves(out["state"]["params"])
    platforms = {d.platform for leaf in params for d in leaf.devices()}
    check(platforms == {jax.devices()[0].platform},
          f"train: params live on {platforms}")
    steady = out["step_seconds"][1:]
    report("train", setup_s=round(wall - sum(steady), 3),
           step_ms=round(1e3 * sum(steady) / len(steady), 3),
           losses=[round(x, 4) for x in losses])
    return out


def serve_phase(reqs):
    from repro.core.compositional import is_quantized_table
    from repro.launch import serve
    from repro.models.dlrm import dlrm_forward
    from repro.serve.quantize import dequantize_table

    t0 = time.monotonic()
    engine = serve.main([
        "--arch", ARCH, size_flag(), "--quantize", "int8",
        "--cache-impl", "device", "--batching", "continuous",
        "--requests", str(REQUESTS), "--batch-size", str(SERVE_BATCH),
        "--max-bag", str(MAX_BAG)])
    setup = time.monotonic() - t0
    m = engine.metrics()
    paths = m["paths"]
    check(sum(paths.values()) == m["waves"],
          f"serve: wave paths {paths} do not cover {m['waves']} waves")
    first = np.asarray([engine.completed[u].score for u in range(REQUESTS)],
                       np.float32)
    engine.reset_metrics()
    t1 = time.monotonic()
    warm = serve_scores(engine, reqs)        # same requests, compiled shapes
    run = time.monotonic() - t1
    check(np.array_equal(first, warm),
          "serve: a warm rerun of the same requests changed the scores")

    cfg = engine.cfg
    deq = jax.tree.map(
        lambda t: dequantize_table(t) if is_quantized_table(t) else t,
        engine.params, is_leaf=is_quantized_table)
    dense, idx, mask = padded(reqs, len(cfg.table_sizes))
    want = np.asarray(jax.jit(
        lambda p, d, i, m: dlrm_forward(p, d, i, cfg, mask=m))(
            deq, dense, idx, mask), np.float32)
    err = float(np.max(np.abs(first - want)))
    check(bool(np.isfinite(first).all()) and err <= SCORE_ATOL,
          f"serve: engine vs jnp dlrm_forward max |diff| {err}")
    report("serve", setup_s=round(setup, 3), run_s=round(run, 3),
           requests=REQUESTS, waves_first_pass=paths,
           waves_warm_pass=engine.metrics()["paths"],
           max_abs_diff_vs_ref=err)
    return engine, first


def kernel_phase(engine, reqs, jnp_scores):
    from repro.models.dlrm import dlrm_forward
    from repro.serve.recsys import RecsysEngine

    kcfg = dataclasses.replace(engine.cfg, use_kernel=True)
    # no cache: every wave takes the in-graph embed, i.e. the fused kernel
    keng = RecsysEngine(kcfg, engine.params, max_batch=SERVE_BATCH)
    t0 = time.monotonic()
    got = serve_scores(keng, reqs)
    setup = time.monotonic() - t0
    t1 = time.monotonic()
    warm = serve_scores(keng, reqs)
    run = time.monotonic() - t1
    err = float(np.max(np.abs(got - jnp_scores)))
    check(bool(np.isfinite(got).all()) and err <= SCORE_ATOL
          and np.array_equal(got, warm),
          f"kernels: kernel vs jnp scores max |diff| {err}")

    dense, idx, mask = padded(reqs[:SERVE_BATCH], len(kcfg.table_sizes))
    hlo = jax.jit(lambda p, d, i, m: dlrm_forward(p, d, i, kcfg, mask=m)
                  ).lower(engine.params, dense, idx, mask).compile().as_text()
    calls = hlo.count("tpu_custom_call")
    check(calls > 0, "kernels: use_kernel forward has no tpu_custom_call")
    report("kernels", setup_s=round(setup, 3), run_s=round(run, 3),
           requests=REQUESTS, waves=keng.metrics()["paths"],
           tpu_custom_calls=calls, max_abs_diff_vs_jnp=err)


def sharded_serve_phase(reqs):
    from repro.configs import get_arch
    from repro.serve.cache import DeviceHotRowCache
    from repro.serve.quantize import quantize_params
    from repro.serve.recsys import RecsysEngine

    t0 = time.monotonic()
    mod = get_arch(ARCH)
    cfg = mod.config(reduced=REDUCED)
    qparams = quantize_params(mod.api(cfg).init(jax.random.PRNGKey(0)),
                              mode="int8")
    # the parity setup of tests/test_serve_dist.py: lock-step waves, the
    # sharded engine's per-device batch equal to the single engine's batch
    single = RecsysEngine(cfg, qparams, max_batch=SERVE_BATCH,
                          batching="waves")
    sharded = RecsysEngine(cfg, qparams, max_batch=SERVE_BATCH * SHARDED_CHIPS,
                           batching="waves", mesh_devices=SHARDED_CHIPS,
                           cache=DeviceHotRowCache(capacity_rows=4096))
    want = serve_scores(single, reqs)
    got = serve_scores(sharded, reqs)
    setup = time.monotonic() - t0
    t1 = time.monotonic()
    warm = serve_scores(sharded, reqs)
    run = time.monotonic() - t1
    pl = sharded.placement
    report("sharded_serve", setup_s=round(setup, 3), run_s=round(run, 3),
           requests=REQUESTS, sharded_sub_tables=len(pl.sharded),
           replicated_sub_tables=len(pl.replicated),
           bitwise_parity=bool(np.array_equal(got, want)),
           max_abs_diff=float(np.max(np.abs(got - want))),
           waves=sharded.metrics()["paths"])
    check(bool(np.isfinite(got).all()) and np.array_equal(got, warm),
          "sharded_serve: non-finite or unstable scores")
    check(np.array_equal(got, want),
          "sharded_serve: sharded scores differ from the single-chip engine")


def dp_phase():
    out = train_phase(train_args() + ["--compress-policy", "int8"])
    for leaf in jax.tree.leaves(out["state"]["params"]):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        check(len(shards) == SHARDED_CHIPS
              and all(np.array_equal(shards[0], s) for s in shards[1:]),
              "dp: replica parameter shards differ")
    report("dp", replicas=SHARDED_CHIPS, shards_bitwise_identical=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, SHARDED_CHIPS),
                    default=1, help="1: train/serve/kernel phases; "
                    f"{SHARDED_CHIPS}: sharded serving and the int8 DP step")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (found {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    report("device", platform=devices[0].platform,
           kind=devices[0].device_kind, count=len(devices),
           compile_cache=enable_compile_cache())

    reqs = requests()
    if args.chips == 1:
        train_phase(train_args())
        engine, scores = serve_phase(reqs)
        kernel_phase(engine, reqs, scores)
    else:
        sharded_serve_phase(reqs)
        dp_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
