import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST run before any other import (jax locks the
# device count at first init).  Everything below is ordinary code.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves, without hardware, that the distribution config
is coherent: shardings propagate, collectives lower, and the per-chip
memory/compute footprint is what the roofline analysis consumes.

Artifacts: ``artifacts/dryrun/<arch>__<shape>__<mesh>.json`` with
  * memory_analysis (per-device argument/temp/output bytes),
  * XLA cost_analysis (unscaled) + our scan-aware HLO analysis
    (flops / HBM bytes / collective wire bytes per chip, collective mix),
  * lower/compile wall times.

Resumable: existing artifacts are skipped unless --force.

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape decode_32k
  python -m repro.launch.dryrun --all                  # every cell, both meshes
  python -m repro.launch.dryrun --all --mesh single    # single-pod only
"""

import argparse
import json
import time
import traceback


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             force: bool = False, embedding: str = "qr") -> dict:
    import jax

    from ..configs import get_arch, lowerables
    from .hlo_analysis import analyze_compiled
    from .mesh import make_production_mesh

    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    tag = f"{arch}__{shape}__{mesh_name}"
    path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    os.makedirs(out_dir, exist_ok=True)

    record = {"arch": arch, "shape": shape, "mesh": mesh_name,
              "embedding": embedding, "ok": False}
    t0 = time.monotonic()
    try:
        mod = get_arch(arch)
        cfg = mod.config(embedding=embedding)
        api = mod.api(cfg)
        mesh = make_production_mesh(multi_pod=multi_pod)
        fn, args = lowerables(api, shape, mesh)
        from ..configs import SHAPES
        kind = SHAPES[shape].kind
        # donate the mutable aggregate (train state / decode+prefill cache):
        # without donation XLA double-buffers multi-GB state trees.
        donate = {"train": (0,), "prefill": (len(args) - 1,),
                  "decode": (3,)}[kind]
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
            record["time_lower_s"] = round(time.monotonic() - t0, 2)
            t1 = time.monotonic()
            compiled = lowered.compile()
            record["time_compile_s"] = round(time.monotonic() - t1, 2)
            analysis = analyze_compiled(compiled, total_devices=mesh.size)
            import gzip
            with gzip.open(os.path.join(out_dir, tag + ".hlo.gz"), "wt") as hf:
                hf.write(compiled.as_text())
            print(compiled.memory_analysis())
            print({k: v for k, v in (analysis.get("xla_cost_analysis") or {}).items()})
        record.update(analysis)
        record["devices"] = mesh.size
        record["ok"] = True
    except Exception as e:  # record the failure — these are bugs to fix
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f, indent=1, default=float)
    os.replace(tmp, path)
    status = "OK" if record["ok"] else "FAIL"
    print(f"[{status}] {tag} lower={record.get('time_lower_s')}s "
          f"compile={record.get('time_compile_s')}s "
          f"flops={record.get('flops_per_chip'):.3g}" if record["ok"] else
          f"[FAIL] {tag}: {record.get('error')}")
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--embedding", default="qr")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    from ..configs import cells
    todo = cells() if args.all else [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    n_ok = n_fail = 0
    for arch, shape in todo:
        for mp in meshes:
            rec = run_cell(arch, shape, mp, args.out, force=args.force,
                           embedding=args.embedding)
            n_ok += rec["ok"]
            n_fail += not rec["ok"]
    print(f"dry-run complete: {n_ok} ok, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
