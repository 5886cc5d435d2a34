"""Price of the fixed-order bag sum on the default serving embed program.

    python benchmarks/bag_sum_cost.py [--out FILE] [--reduced]

Compiles and times ``RecsysEngine``'s in-graph embed program (dlrm-criteo,
Kaggle size unless ``--reduced``, int8 tables) twice per shape: once with
``core.compositional.masked_bag_sum`` (the fixed order every pooling path
uses) and once with a plain ``reduce`` over the bag, whose order XLA picks
per fusion.  Shapes are batch B in {32, 256} and bag length L in {4, 16,
64}; each variant is compiled twice in the order reduce, fixed, fixed,
reduce.  Per call time is host clock over 20 pipelined calls, median of 5.
One JSON line per compile, plus the largest output difference per shape.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs import get_arch  # noqa: E402
from repro.core import compositional  # noqa: E402
from repro.models.dlrm import embed_features  # noqa: E402
from repro.serve.quantize import quantize_params  # noqa: E402

FIXED = compositional.masked_bag_sum


def reduce_sum(rows, mask=None):
    x = rows.astype(jnp.float32)
    if mask is not None:
        x = x * mask[..., None].astype(jnp.float32)
    return x.sum(axis=-2)


def time_call(compiled, *args):
    out = compiled(*args)
    jax.block_until_ready(out)
    reps = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(20):
            out = compiled(*args)
        jax.block_until_ready(out)
        reps.append((time.perf_counter() - t0) / 20 * 1e3)
    return np.asarray(out), reps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", help="also write all rows to this JSON file")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced table sizes (for a run without a chip)")
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    mod = get_arch("dlrm-criteo")
    cfg = mod.config(reduced=args.reduced)
    params = quantize_params(mod.api(cfg).init(jax.random.PRNGKey(0)),
                             mode="int8")
    rng = np.random.default_rng(0)
    sizes = np.asarray(cfg.table_sizes)
    rows = []
    try:
        for b in (32, 256):
            for length in (4, 16, 64):
                shape = (b, len(sizes), length)
                idx = jnp.asarray(
                    (rng.random(shape) * sizes[None, :, None]).astype(np.int32))
                mask = jnp.asarray((rng.random(shape) < 0.8).astype(np.float32))
                outs = {}
                for name in ("reduce", "fixed", "fixed", "reduce"):
                    compositional.masked_bag_sum = (
                        FIXED if name == "fixed" else reduce_sum)
                    # a fresh trace per variant picks up the patched sum
                    fn = jax.jit(lambda p, i, m: jnp.stack(embed_features(  # repro: noqa[JIT-001] each compile is what is measured
                        p["tables"], i, cfg, mask=m), axis=1))
                    t0 = time.perf_counter()
                    compiled = fn.lower(params, idx, mask).compile()
                    compile_s = time.perf_counter() - t0
                    out, reps = time_call(compiled, params, idx, mask)
                    outs[name] = out
                    row = {"B": b, "L": length, "variant": name,
                           "compile_s": compile_s,
                           "call_ms_median": float(np.median(reps)),
                           "call_ms_all": reps}
                    rows.append(row)
                    print(json.dumps(row), flush=True)
                diff = float(np.max(np.abs(outs["reduce"] - outs["fixed"])))
                print(json.dumps({"B": b, "L": length, "max_abs_diff": diff}),
                      flush=True)
    finally:
        compositional.masked_bag_sum = FIXED
    if args.out:
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
