"""Layer-2 collective auditor: ``dist.accounting`` closed forms must
equal lowered-HLO wire bytes for every registered exchange — exactly.

Generalizes the dist_bench / serve_dist_bench spot checks into one pass
over a program registry.  Each program is the *pure exchange* (not a
full train step): the dp compressed all-reduce (``ef_psum_grads``), the
FSDP compressed reduce-scatter + f32 param all-gather, and the sharded
serve row exchange (``exchange_rows``), compiled on the host mesh and
priced by ``launch.hlo_analysis.analyze_hlo``.  Pure exchanges carry no
optimizer fusion noise, so the tolerance is **zero bytes** — any drift
between a closed form and what XLA actually puts on the wire is a bug
in one of them.

Programs compile to HLO text only — nothing executes.  Needs >= 2
devices (CI forces 8 host devices via XLA_FLAGS); on one device the
pass emits a loud finding rather than passing vacuously.

``REPRO_ANALYSIS_INJECT=wire`` perturbs the closed form (test hook,
mirroring ``REPRO_BENCH_INJECT_ERROR``) so the fixture suite can prove
a real mismatch fails the run.
"""

from __future__ import annotations

import os

from .findings import Finding
from .registry import Context, register_pass

__all__ = ["wire_programs", "audit_exchange"]

_RULE = "WIRE-001"


def _mesh_and_n():
    import jax
    n = jax.device_count()
    if n < 2:
        return None, n
    from ..launch.mesh import make_mesh
    return make_mesh((n,), ("data",)), n


def _dp_psum(mode: str):
    """(name, build) for the compressed dp mean-all-reduce of a small
    grads tree — the exchange ``make_dp_train_step`` runs per step."""
    def build(mesh, n):
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from ..dist import accounting
        from ..dist.compress import ef_psum_grads, init_error_state
        grads = {"table": jnp.zeros((64, 16)), "w": jnp.zeros((33, 7)),
                 "b": jnp.zeros((7,))}
        err = init_error_state(grads)

        def body(g, e):
            return ef_psum_grads(g, e, axis_name="data", mode=mode)

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(), P()),
                               out_specs=(P(), P()), check_vma=False))
        lowered = fn.lower(grads, err)
        closed = accounting.grad_wire_bytes(
            grads, mode, n, pattern="all_reduce")["total_bytes"]
        return lowered, closed
    return f"dp_psum[{mode}]", build


def _fsdp(mode: str):
    """Compressed reduce-scatter per leaf + f32 all-gather of the updated
    shard — the two collectives of ``make_fsdp_train_step``."""
    def build(mesh, n):
        import math
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from ..dist import accounting
        from ..dist.compress import _reduce_scatter_leaf, init_error_state
        leaves = {"table": jnp.zeros((64, 16)), "w": jnp.zeros((40, 8))}
        err = init_error_state(leaves)

        def body(g, e):
            outs, new_e = {}, {}
            for k in g:
                shard, ne = _reduce_scatter_leaf(g[k], e[k], "data", mode, 0)
                outs[k] = jax.lax.all_gather(shard, "data", tiled=True)
                new_e[k] = ne
            return outs, new_e

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(), P()),
                               out_specs=(P(), P()), check_vma=False))
        lowered = fn.lower(leaves, err)
        closed = sum(
            accounting.leaf_reduce_bytes(mode, math.prod(v.shape), n,
                                         pattern="reduce_scatter")
            + accounting.ring_all_gather_bytes(4.0 * math.prod(v.shape), n)
            for v in leaves.values())
        return lowered, closed
    return f"fsdp_rs_gather[{mode}]", build


def _serve_exchange(quantized: bool):
    """The two-phase sharded-serve row fetch (``exchange_rows``) for one
    sub-table and wave."""
    def build(mesh, n):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from ..dist import accounting
        from ..dist.serve_placement import exchange_rows
        from ..serve.quantize import quantize_table
        rows_total, width, lookups = 8 * n, 16, 24
        rpd = rows_total // n
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(rows_total, width)), jnp.float32)
        ids = jnp.asarray(rng.integers(0, rows_total, (n, lookups)),
                          jnp.int32)
        leaf = quantize_table(w) if quantized else w
        spec = ({"q": P("data"), "scale": P("data"), "zp": P("data")}
                if quantized else P("data"))

        def body(leaf, ids):
            return exchange_rows(leaf, ids, n, rpd, axis="data")

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec, P("data")),
                               out_specs=P("data"), check_vma=False))
        lowered = fn.lower(leaf, ids)
        closed = accounting.serve_exchange_wire_bytes(
            lookups, width, n, quantized=quantized,
            row_dtype_bytes=4)["total_bytes"]
        return lowered, closed
    return f"serve_exchange[{'int8' if quantized else 'f32'}]", build


def wire_programs():
    """Every registered (name, build) exchange the auditor certifies."""
    progs = [_dp_psum(m) for m in ("none", "bf16", "int8")]
    progs += [_fsdp(m) for m in ("none", "bf16", "int8")]
    progs += [_serve_exchange(q) for q in (False, True)]
    return progs


def audit_exchange(name, build, mesh, n) -> tuple[Finding | None, dict]:
    """Compile one exchange and compare closed-form vs HLO bytes."""
    from ..launch.hlo_analysis import analyze_hlo
    anchor = f"analysis://wire/{name}"
    try:
        lowered, closed = build(mesh, n)
        compiled = lowered.compile()
        cost = analyze_hlo(compiled.as_text(), total_devices=n)
    except Exception as e:
        return (Finding(rule=_RULE, path=anchor, line=0, layer=2,
                        message=f"exchange failed to compile: {e!r}"),
                {"name": name, "error": repr(e)})
    if os.environ.get("REPRO_ANALYSIS_INJECT") == "wire":
        closed += 64.0   # test hook: prove a mismatch fails the run
    row = {"name": name, "closed_form_bytes": closed,
           "hlo_bytes": cost.collective_bytes, "devices": n}
    if abs(closed - cost.collective_bytes) > 1e-6:
        return (Finding(
            rule=_RULE, path=anchor, line=0, layer=2,
            message=f"accounting closed form ({closed:.0f} B) != compiled "
                    f"HLO wire bytes ({cost.collective_bytes:.0f} B) on "
                    f"{n} devices — dist.accounting and the lowered "
                    "exchange have drifted apart"), row)
    return None, row


@register_pass(_RULE, "wire-accounting", 2,
               "dist.accounting closed forms == lowered-HLO wire bytes "
               "for every registered exchange")
def wire_pass(ctx: Context) -> list[Finding]:
    mesh, n = _mesh_and_n()
    if mesh is None:
        return [Finding(
            rule=_RULE, path="analysis://wire", line=0, layer=2,
            message=f"only {n} device(s) visible — the wire audit needs a "
                    "multi-device mesh (set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8); refusing "
                    "to pass vacuously")]
    findings, rows = [], []
    for name, build in wire_programs():
        f, row = audit_exchange(name, build, mesh, n)
        rows.append(row)
        if f is not None:
            findings.append(f)
    ctx.notes[_RULE] = {"exchanges": rows}
    return findings
