"""Sharded serving: plan-aware placement + exchange + engine parity.

Host-side cases (placement policy, byte accounting, spec emission, plan
annotation round-trip, engine validation) run on the single-device view.
The multi-device cases — exchange bitwise-vs-local, sharded-vs-single-
host engine parity (uniform and mixed-width plans, empty bags, device
cache on) — run in a subprocess with 8 forced host devices, one bundle
per process to amortize the mesh startup (the test_dist.py idiom).
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.dist.accounting import (ring_all_to_all_bytes,
                                   serve_exchange_wire_bytes,
                                   serve_wave_wire_bytes)
from repro.dist.serve_placement import (ServePlacement, plan_placement,
                                        sub_table_items)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _quantized_dlrm(emb_dim=16):
    import dataclasses

    from repro.configs import dlrm_criteo
    from repro.serve.quantize import quantize_params

    cfg = dataclasses.replace(dlrm_criteo.config(reduced=True),
                              emb_dim=emb_dim)
    api = dlrm_criteo.api(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return cfg, params, quantize_params(params, mode="int8")


# ------------------------------------------------------------ placement


def test_plan_placement_bounds_per_device_bytes():
    cfg, _, qparams = _quantized_dlrm()
    n = 8
    pl = plan_placement(qparams, n)
    assert pl.n_devices == n
    assert len(pl.entries) == len(sub_table_items(qparams))
    assert pl.sharded, "nothing sharded — threshold too high for the config"
    for e in pl.sharded:
        assert e.rows >= n and e.bytes_total > pl.threshold_bytes
        assert e.padded_rows % n == 0 and e.padded_rows >= e.rows
        assert pl.rows_per_device(e) * n == e.padded_rows
    for e in pl.replicated:
        assert e.padded_rows == e.rows
    # the acceptance bound the bench gates on, from the placement's own
    # accounting: every device holds the replicated set + 1/N of the rest
    assert pl.bytes_per_device() <= (pl.total_bytes() // n
                                     + pl.replicated_bytes() + pl.pad_bytes())


def test_plan_placement_single_device_replicates_everything():
    _, _, qparams = _quantized_dlrm()
    pl = plan_placement(qparams, 1)
    assert not pl.sharded
    assert pl.bytes_per_device() == pl.total_bytes()
    assert bool(pl.replicated_features(len(pl.entries)).all())


def test_placement_round_trips_through_plan_json():
    from repro.plan import plan_for_config

    cfg, _, qparams = _quantized_dlrm()
    plan = plan_for_config(cfg, 1 << 18, bytes_domain="serve_int8",
                           num_batches=4, batch_size=128)
    pl = plan_placement(qparams, 8, plan=plan)
    # threshold derives from the plan's byte claim, not the built params
    assert pl.threshold_bytes == max(1, plan.total_bytes // (4 * 8))
    plan.annotate_placement(pl)
    back = type(plan).from_json(plan.to_json()).serve_placement()
    assert back is not None and back.as_dict() == pl.as_dict()
    assert ServePlacement.from_dict(pl.as_dict()).as_dict() == pl.as_dict()


def test_replicated_features_masks_row_sharded_features():
    _, _, qparams = _quantized_dlrm()
    pl = plan_placement(qparams, 8)
    f = len(qparams["tables"])
    mask = pl.replicated_features(f)
    sharded_feats = {e.feature for e in pl.sharded}
    for i in range(f):
        assert mask[i] == (i not in sharded_feats)


def test_placement_specs_shard_rows_only():
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import placement_specs

    _, _, qparams = _quantized_dlrm()
    pl = plan_placement(qparams, 8)
    # pad sharded leaves the way place_params would before fitting specs
    import jax.numpy as jnp

    def pad(leaf, rows):
        return jnp.concatenate(
            [leaf, jnp.zeros((rows - leaf.shape[0],) + leaf.shape[1:],
                             leaf.dtype)])
    padded = jax.tree.map(lambda x: x, qparams)  # fresh containers
    for e in pl.sharded:
        sub = padded["tables"][e.feature][e.table_key]
        for k in sub:
            sub[k] = pad(sub[k], e.padded_rows)
    specs = placement_specs(padded, pl)
    sharded_paths = {(e.feature, e.table_key) for e in pl.sharded}
    for i, tp in enumerate(specs["tables"]):
        for key, sub in tp.items():
            for spec in jax.tree.leaves(sub, is_leaf=lambda s:
                                        isinstance(s, P)):
                if (i, key) in sharded_paths:
                    assert spec[0] == "data", (i, key, spec)
                else:
                    assert all(ax is None for ax in spec), (i, key, spec)


# ------------------------------------------------------------ accounting


def test_serve_exchange_wire_bytes_closed_form():
    n, lookups, width = 8, 96, 32
    q = serve_exchange_wire_bytes(lookups, width, n, quantized=True)
    ids = ring_all_to_all_bytes(4.0 * n * lookups, n)
    rows = (ring_all_to_all_bytes(1.0 * n * lookups * width, n)
            + ring_all_to_all_bytes(2.0 * n * lookups, n)
            + ring_all_to_all_bytes(1.0 * n * lookups, n))
    assert q["ids_bytes"] == ids
    assert q["total_bytes"] == ids + rows
    d = serve_exchange_wire_bytes(lookups, width, n, quantized=False)
    assert d["rows_bytes"] == ring_all_to_all_bytes(
        4.0 * n * lookups * width, n)
    # int8-on-the-wire beats f32 rows once width amortizes the meta
    assert q["rows_bytes"] < d["rows_bytes"]


def test_serve_wave_wire_bytes_sums_sharded_entries():
    _, _, qparams = _quantized_dlrm()
    pl = plan_placement(qparams, 8)
    acct = serve_wave_wire_bytes(pl, batch_per_device=32, bag_len=4)
    assert acct["lookups_per_device"] == 128
    assert len(acct["per_entry"]) == len(pl.sharded)
    assert acct["total_bytes"] == sum(e["total_bytes"]
                                      for e in acct["per_entry"])
    none_sharded = plan_placement(qparams, 1)
    assert serve_wave_wire_bytes(none_sharded, 32, 4)["total_bytes"] == 0


# ------------------------------------------------------------ validation


def test_engine_sharded_mode_validation():
    import dataclasses

    from repro.serve.cache import HotRowCache
    from repro.serve.recsys import RecsysEngine

    cfg, _, qparams = _quantized_dlrm()
    with pytest.raises(ValueError, match="multiple of"):
        RecsysEngine(cfg, qparams, max_batch=12, mesh_devices=8)
    with pytest.raises(NotImplementedError, match="DeviceHotRowCache"):
        RecsysEngine(cfg, qparams, max_batch=16, mesh_devices=8,
                     cache=HotRowCache())
    kcfg = dataclasses.replace(cfg, use_kernel=True)
    with pytest.raises(NotImplementedError, match="kernel"):
        RecsysEngine(kcfg, qparams, max_batch=16, mesh_devices=8)


# ------------------------------------------------------------ 8-device


_CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import numpy as np
    import jax, jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.configs import dlrm_criteo
    from repro.core.compositional import table_rows
    from repro.dist.serve_placement import exchange_rows, plan_placement
    from repro.launch.mesh import make_mesh
    from repro.plan import plan_for_config
    from repro.serve.cache import DeviceHotRowCache
    from repro.serve.quantize import quantize_params
    from repro.serve.recsys import RecsysEngine, _FEATURE_SHIFT

    out = {}
    n = 8
    mesh = make_mesh((n,), ("data",))

    # --- exchange_rows vs local table_rows: bitwise, f32 and quantized
    rng = np.random.default_rng(0)
    rows, width = 64, 12
    table = jnp.asarray(rng.normal(size=(rows, width)).astype(np.float32))
    qt = {"q": jnp.asarray(rng.integers(-128, 128, (rows, width)), jnp.int8),
          "scale": jnp.asarray(rng.random((rows, 1)).astype(np.float32) / 10
                               ).astype(jnp.bfloat16),
          "zp": jnp.asarray(rng.integers(-8, 8, (rows, 1)), jnp.int8)}
    ids = jnp.asarray(rng.integers(0, rows, (16, 5)), jnp.int32)

    def run_ex(leaf):
        fn = shard_map(
            lambda l, i: exchange_rows(l, i, n, rows // n, axis="data"),
            mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P("data"))
        return jax.jit(fn)(leaf, ids)

    got = np.asarray(run_ex(table))
    want = np.asarray(table_rows(table, ids))
    out["exchange_f32_bitwise"] = bool(np.array_equal(got, want))
    got_q = np.asarray(run_ex(qt))
    want_q = np.asarray(table_rows(qt, ids))
    out["exchange_quant_bitwise"] = bool(np.array_equal(got_q, want_q))

    # --- engine parity: sharded vs single-host, waves mode
    def stream(cfg, count, max_bag=8):
        r = np.random.default_rng(1)
        reqs = []
        f = len(cfg.table_sizes)
        for k in range(count):
            L = max_bag if k % 32 == 0 else 1 + (k * 7) % max_bag
            dense = r.normal(size=(13,)).astype(np.float32)
            bags = [list((r.integers(0, s, size=L)).astype(int))
                    for s in cfg.table_sizes]
            if k % 4 == 1:
                bags[k % f] = []          # empty bag -> zero-vector pool
            reqs.append((dense, bags))
        return reqs

    def scores(engine, reqs):
        uids = [engine.submit(d, b) for d, b in reqs]
        done = engine.run_until_drained()
        return np.asarray([done[u].score for u in uids], np.float32)

    def parity(cfg, qparams, reqs, cache=None):
        e1 = RecsysEngine(cfg, qparams, max_batch=16, batching="waves")
        e8 = RecsysEngine(cfg, qparams, max_batch=128, batching="waves",
                          mesh_devices=n, cache=cache)
        return scores(e1, reqs), scores(e8, reqs), e8

    cfg, qp = None, None
    cfg = dataclasses.replace(dlrm_criteo.config(reduced=True), emb_dim=16)
    api = dlrm_criteo.api(cfg)
    qp = quantize_params(api.init(jax.random.PRNGKey(0)), mode="int8")
    reqs = stream(cfg, 128)
    s1, s8, _ = parity(cfg, qp, reqs)
    out["parity_uniform_bitwise"] = bool(np.array_equal(s1, s8))

    # --- mixed-width plan (distinct per-feature dims + projections)
    plan = plan_for_config(cfg, 1 << 17, bytes_domain="serve_int8",
                           num_batches=4, batch_size=128, dims=(4, 8, 16))
    mcfg = dlrm_criteo.config(reduced=True, plan=plan)
    mapi = dlrm_criteo.api(mcfg)
    mqp = quantize_params(mapi.init(jax.random.PRNGKey(1)), mode="int8")
    out["mixed_widths"] = len(set(plan.table_dims)) > 1
    mreqs = stream(mcfg, 128)
    m1, m8, _ = parity(mcfg, mqp, mreqs)
    out["parity_mixed_bitwise"] = bool(np.array_equal(m1, m8))

    # --- device cache on: parity, hits, and locality of admitted keys
    cache = DeviceHotRowCache(capacity_rows=1 << 14)
    c1, c8, e8c = parity(cfg, qp, reqs, cache=cache)
    out["parity_cache_bitwise"] = bool(np.array_equal(c1, c8))
    scores(e8c, reqs)                      # second pass hits the cache
    out["cache_hit_rate"] = float(e8c.metrics()["cache"]["hit_rate"])
    keys, _ = cache.slot_items()
    feats = set((np.asarray(keys) >> _FEATURE_SHIFT).tolist())
    repl = {i for i in range(len(cfg.table_sizes))
            if e8c.placement.replicated_features(len(cfg.table_sizes))[i]}
    out["cache_keys_replicated_only"] = feats <= repl and bool(feats)

    print(json.dumps(out))
""")


@pytest.mark.slow
def test_sharded_serving_8dev_bundle():
    res = subprocess.run([sys.executable, "-c", _CHILD],
                         capture_output=True, text=True,
                         env=dict(os.environ, PYTHONPATH=f"{REPO}/src"),
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["exchange_f32_bitwise"]
    assert out["exchange_quant_bitwise"]
    assert out["parity_uniform_bitwise"]
    assert out["mixed_widths"]
    assert out["parity_mixed_bitwise"]
    assert out["parity_cache_bitwise"]
    assert out["cache_hit_rate"] > 0
    assert out["cache_keys_replicated_only"]
