"""repro.serve v2: quantization, fused dequant kernel, hot-row cache,
and the microbatched RecsysEngine (bucket-padding correctness)."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EmbeddingSpec, table_rows
from repro.kernels import ops, ref
from repro.kernels.serve_path import fused_serve_pool
from repro.launch.mesh import make_mesh
from repro.models.dcn import DCNConfig, dcn_init
from repro.models.dlrm import (DLRMConfig, dlrm_forward, dlrm_init,
                               dlrm_loss_fn)
from repro.serve.cache import HotRowCache
from repro.serve.quantize import (dequantize_rows, dequantize_table,
                                  is_quantized_table, memory_report,
                                  paths_and_leaves, quantize_params,
                                  quantize_table)
from repro.serve.recsys import RecsysEngine

SIZES = (100, 500, 33)


def _cfg(**kw):
    base = dict(table_sizes=SIZES, emb_dim=16, bottom_mlp=(32, 16),
                top_mlp=(32,),
                embedding=EmbeddingSpec(kind="qr", num_collisions=4,
                                        threshold=40))
    base.update(kw)
    return DLRMConfig(**base)


def _requests(n, seed=0, sizes=SIZES, max_bag=3):
    rng = np.random.default_rng(seed)
    return [(rng.normal(size=13),
             [list(rng.integers(0, s, size=rng.integers(1, max_bag + 1)))
              for s in sizes])
            for _ in range(n)]


# ------------------------------------------------------------- quantization


def test_quantize_per_row_error_bound():
    """|dequant - w| <= scale/2 per row, even with per-row magnitude skew."""
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (64, 32)) \
        * jnp.exp(2.0 * jax.random.normal(jax.random.PRNGKey(1), (64, 1)))
    qt = quantize_table(w)
    err = np.abs(np.asarray(dequantize_table(qt)) - np.asarray(w, np.float32))
    bound = 0.5 * np.asarray(qt["scale"], np.float32)
    assert (err <= bound + 1e-7).all()
    # per-row scales actually differ (the point of row-wise quantization)
    scales = np.asarray(qt["scale"], np.float32).ravel()
    assert scales.max() / scales.min() > 10


def test_quantize_degenerate_rows():
    # all-zero row: exact; constant positive row: zero must stay on-grid
    w = jnp.stack([jnp.zeros((8,)), jnp.full((8,), 2.5),
                   jnp.full((8,), -1e-30)])
    qt = quantize_table(w)
    deq = np.asarray(dequantize_table(qt))
    np.testing.assert_array_equal(deq[0], 0.0)
    np.testing.assert_allclose(deq[1], 2.5, rtol=1e-2)
    assert np.isfinite(np.asarray(qt["scale"], np.float32)).all()
    assert qt["q"].dtype == jnp.int8 and qt["zp"].dtype == jnp.int8


def test_quantize_gathers_only_requested_rows():
    w = jax.random.normal(jax.random.PRNGKey(2), (20, 8))
    qt = quantize_table(w)
    idx = jnp.asarray([3, 3, 19, 0])
    rows = dequantize_rows(qt, idx)
    assert rows.shape == (4, 8) and rows.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(rows),
                               np.asarray(dequantize_table(qt))[np.asarray(idx)],
                               rtol=1e-6)
    # table_rows is the shared gather: dense and quantized agree to bound
    np.testing.assert_allclose(np.asarray(rows),
                               np.asarray(table_rows(qt, idx)), rtol=1e-6)


def test_quantize_params_only_touches_tables():
    cfg = _cfg()
    params = dlrm_init(jax.random.PRNGKey(0), cfg)
    qp = quantize_params(params)
    # every table leaf quantized, every MLP leaf untouched
    for path, leaf in paths_and_leaves(qp):
        if "table" in path:
            assert is_quantized_table(leaf), path
        else:
            assert not is_quantized_table(leaf) and leaf.dtype == jnp.float32, path
    # bf16 mode: same structure, tables cast
    bp = quantize_params(params, mode="bf16")
    for path, leaf in paths_and_leaves(bp):
        want = jnp.bfloat16 if "table" in path else jnp.float32
        assert leaf.dtype == want, path
    assert quantize_params(params, mode="f32") is params
    with pytest.raises(ValueError):
        quantize_params(params, mode="fp4")


def test_memory_report_int8_ratio_at_serve_dim():
    """At the deployment dim (D=64) int8 tables beat the 0.27x bar;
    bf16 is exactly 0.5x."""
    cfg = _cfg(emb_dim=64)
    params = dlrm_init(jax.random.PRNGKey(0), cfg)
    rep = memory_report(params, quantize_params(params))
    assert rep["ratio"] <= 0.27, rep
    rep_bf = memory_report(params, quantize_params(params, mode="bf16"))
    assert abs(rep_bf["ratio"] - 0.5) < 1e-6
    assert rep["model_bytes_quant"] < rep["model_bytes_f32"]


# ------------------------------------------------------- fused dequant kernel


@pytest.mark.parametrize("op", ["mult", "add"])
@pytest.mark.parametrize("m,q,d,n", [(7, 3, 16, 5), (64, 8, 128, 33)])
def test_qr_gather_quant_kernel_matches_oracle(op, m, q, d, n):
    """Kernel (int8 gather + VMEM dequant + combine) == jnp dequant oracle
    bitwise, and tracks the f32-table oracle within the propagated
    per-row-scale bound."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    wr = jax.random.normal(k1, (m, d))
    wq = jax.random.normal(k2, (q, d))
    qr_, qq_ = quantize_table(wr), quantize_table(wq)
    idx = jax.random.randint(jax.random.PRNGKey(4), (n,), 0, m * q)
    rem, quo = idx % m, idx // m
    meta_r = jnp.concatenate([qr_["scale"].astype(jnp.float32),
                              qr_["zp"].astype(jnp.float32)], axis=1)
    meta_q = jnp.concatenate([qq_["scale"].astype(jnp.float32),
                              qq_["zp"].astype(jnp.float32)], axis=1)
    got = fused_serve_pool(rem[:, None], None, qr_["q"], idx_b=quo[:, None],
                           w_b=qq_["q"], meta_a=meta_r, meta_b=meta_q, op=op,
                           interpret=True)
    want = ref.qr_gather_quant_ref(rem, quo, qr_["q"], qq_["q"],
                                   meta_r, meta_q, op=op)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    f32 = ref.qr_gather_ref(rem, quo, wr, wq, op=op)
    a = np.asarray(jnp.take(wr, rem, axis=0))
    b = np.asarray(jnp.take(wq, quo, axis=0))
    da = 0.5 * np.asarray(qr_["scale"], np.float32)[np.asarray(rem)]
    db = 0.5 * np.asarray(qq_["scale"], np.float32)[np.asarray(quo)]
    if op == "mult":  # |a'b' - ab| <= |a| db + |b| da + da db
        bound = np.abs(a) * db + np.abs(b) * da + da * db
    else:
        bound = da + db
    err = np.abs(np.asarray(got) - np.asarray(f32, np.float32))
    assert (err <= bound + 1e-6).all()


def test_qr_lookup_routes_quantized_tables():
    wr = jax.random.normal(jax.random.PRNGKey(5), (40, 16))
    wq = jax.random.normal(jax.random.PRNGKey(6), (5, 16))
    qr_, qq_ = quantize_table(wr), quantize_table(wq)
    idx = jax.random.randint(jax.random.PRNGKey(7), (2, 9), 0, 200)
    got = ops.qr_lookup(idx, qr_, qq_)                     # fused kernel
    want = ops.qr_lookup(idx, qr_, qq_, use_kernel=False)  # dequant fallback
    assert got.shape == (2, 9, 16) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # concat falls back without the kernel
    cat = ops.qr_lookup(idx, qr_, qq_, op="concat")
    assert cat.shape == (2, 9, 32)


def test_qr_bag_lookup_quantized_mask_semantics():
    """Masked slots of a quantized bag contribute exactly nothing."""
    wr = jax.random.normal(jax.random.PRNGKey(8), (40, 16))
    wq = jax.random.normal(jax.random.PRNGKey(9), (5, 16))
    qr_, qq_ = quantize_table(wr), quantize_table(wq)
    idx = jax.random.randint(jax.random.PRNGKey(10), (4, 6), 0, 200)
    mask = jnp.asarray(np.tile([1, 1, 1, 0, 0, 0], (4, 1)), jnp.float32)
    got = ops.serve_bag_pool(idx, mask, qr_, qq_)
    # garbage in the masked tail must not change the pool
    idx_garbage = idx.at[:, 3:].set(199)
    got2 = ops.serve_bag_pool(idx_garbage, mask, qr_, qq_)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got2))
    want = ops.serve_bag_pool(idx[:, :3], mask[:, :3], qr_, qq_)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# --------------------------------------------------------------- hot-row cache


def test_cache_lru_eviction_order():
    c = HotRowCache(capacity_rows=2, policy="lru", record_events=True)
    r = np.ones(4, np.float32)
    c.put("a", r)
    c.put("b", r)
    assert c.get("a") is not None          # a now more recent than b
    c.put("c", r)                          # evicts b
    assert "b" not in c and "a" in c and "c" in c
    assert ("evict", "b") in c.events
    assert c.stats.evictions == 1 and c.stats.insertions == 3


def test_cache_lfu_keeps_hot_key():
    c = HotRowCache(capacity_rows=2, policy="lfu")
    r = np.ones(4, np.float32)
    c.put("hot", r)
    for _ in range(5):
        c.get("hot")
    c.put("cold", r)
    c.put("new", r)                        # evicts cold (freq 1 < 6)
    assert "hot" in c and "cold" not in c
    assert c.stats.hit_rate == 1.0         # 5 hits, 0 misses so far


def test_cache_deterministic_replay():
    rng = np.random.default_rng(0)
    stream = [("t", int(k), int(k) % 7) for k in rng.integers(0, 40, 300)]
    a = HotRowCache(capacity_rows=16, policy="lfu").replay(stream)
    b = HotRowCache(capacity_rows=16, policy="lfu").replay(stream)
    assert a == b and len(a) >= 300
    lru_a = HotRowCache(capacity_rows=16, policy="lru").replay(stream)
    lru_b = HotRowCache(capacity_rows=16, policy="lru").replay(stream)
    assert lru_a == lru_b
    assert lru_a != a  # the policies genuinely differ on this stream


def test_cache_counters_and_bytes():
    c = HotRowCache(capacity_rows=8)
    row = np.ones(16, np.float32)
    assert c.get("x") is None
    c.put("x", row)
    assert c.get("x") is not None
    assert c.stats.hits == 1 and c.stats.misses == 1
    assert c.stats.bytes_cached == row.nbytes
    found, missing = c.get_many(["x", "y", "x"])
    assert set(found) == {"x"} and missing == ["y"]
    with pytest.raises(ValueError):
        HotRowCache(policy="mru")


def test_cache_byte_budget_admission():
    """capacity_bytes binds independently of capacity_rows: resident bytes
    never exceed the budget, eviction order stays the policy's."""
    from repro.serve.quantize import row_bytes
    d = 16
    row = np.ones(d, np.float32)          # 4*d = row_bytes(d, "f32") bytes
    assert row.nbytes == row_bytes(d, "f32")
    c = HotRowCache(capacity_rows=100, policy="lru",
                    capacity_bytes=3 * row.nbytes, record_events=True)
    for k in "abc":
        c.put(k, row)
    assert len(c) == 3 and c.stats.bytes_cached == 3 * row.nbytes
    c.put("d", row)                        # over budget: evicts LRU "a"
    assert "a" not in c and len(c) == 3
    assert c.stats.bytes_cached <= c.capacity_bytes
    assert ("evict", "a") in c.events


def test_cache_bytes_only_capacity_and_oversized_reject():
    c = HotRowCache(capacity_rows=None, capacity_bytes=100)
    small = np.ones(4, np.float32)         # 16 B
    for k in range(6):                     # 6*16 = 96 B fits
        c.put(k, small)
    assert len(c) == 6 and c.stats.bytes_cached == 96
    c.put(99, small)                       # 112 > 100: evicts one
    assert len(c) == 6 and c.stats.bytes_cached <= 100
    big = np.ones(64, np.float32)          # 256 B > whole budget
    c.put("big", big)                      # rejected, cache untouched
    assert "big" not in c and len(c) == 6
    assert c.stats.rejections == 1
    with pytest.raises(ValueError):
        HotRowCache(capacity_rows=None, capacity_bytes=None)


def test_cache_oversized_refresh_invalidates_not_evicts():
    """A rejected oversized *refresh* of a resident key drops the stale
    value as an invalidation — eviction counts stay capacity-pressure
    only, and the event sequence is pinned."""
    c = HotRowCache(capacity_rows=None, capacity_bytes=100,
                    record_events=True)
    small = np.ones(4, np.float32)         # 16 B
    big = np.ones(64, np.float32)          # 256 B > whole budget
    c.put("k", small)
    c.put("other", small)
    c.put("k", big)                        # oversized refresh of resident k
    assert "k" not in c and "other" in c   # stale value gone, no flush
    assert c.stats.rejections == 1
    assert c.stats.invalidations == 1
    assert c.stats.evictions == 0          # nothing was capacity-evicted
    assert c.events == [("put", "k"), ("put", "other"),
                        ("reject", "k"), ("invalidate", "k")]
    assert c.stats.bytes_cached == small.nbytes
    # a fresh oversized key is a plain rejection: no invalidation
    c.put("new", big)
    assert c.stats.rejections == 2 and c.stats.invalidations == 1
    assert c.stats.as_dict()["invalidations"] == 1


def test_cache_byte_budget_replay_deterministic():
    rng = np.random.default_rng(1)
    stream = [("t", int(k), int(k) % 5) for k in rng.integers(0, 30, 200)]
    kw = dict(capacity_rows=64, capacity_bytes=24 * 16, policy="lfu")
    a = HotRowCache(**kw).replay(stream, row_bytes=16)
    b = HotRowCache(**kw).replay(stream, row_bytes=16)
    assert a == b
    # the byte bound genuinely binds (smaller than the row bound alone)
    unbounded = HotRowCache(capacity_rows=64, policy="lfu")
    unbounded.replay(stream, row_bytes=16)
    bounded = HotRowCache(**kw)
    bounded.replay(stream, row_bytes=16)
    assert bounded.stats.bytes_cached <= 24 * 16
    assert bounded.stats.evictions > unbounded.stats.evictions


# -------------------------------------------------------------- RecsysEngine


def test_engine_bucket_padding_is_exact():
    """Padded bag slots and padded batch rows must not change any score:
    engine (padded/bucketed) == direct per-request forward (exact shapes)."""
    cfg = _cfg()
    params = dlrm_init(jax.random.PRNGKey(0), cfg)
    reqs = _requests(11)  # odd count -> batch padding in the last wave
    # legacy lock-step mode: FIFO slices make the wave/bucket accounting
    # below exact (continuous batching groups by bag-length bucket instead)
    eng = RecsysEngine(cfg, params, max_batch=4, batching="waves")
    uids = [eng.submit(d, b) for d, b in reqs]
    done = eng.run_until_drained()
    for uid, (dense, bags) in zip(uids, reqs):
        lmax = max(len(b) for b in bags)
        idx = np.zeros((1, len(bags), lmax), np.int32)
        mask = np.zeros((1, len(bags), lmax), np.float32)
        for i, bag in enumerate(bags):
            idx[0, i, :len(bag)] = bag
            mask[0, i, :len(bag)] = 1.0
        want = float(dlrm_forward(params, jnp.asarray(dense[None], jnp.float32),
                                  jnp.asarray(idx), cfg,
                                  mask=jnp.asarray(mask))[0])
        assert abs(done[uid].score - want) < 1e-4, uid
    m = eng.metrics()
    assert m["requests"] == 11 and m["waves"] == 3
    assert all(b in ((1, 1), (2, 2), (4, 4), (1, 2), (2, 4), (4, 2), (1, 4),
                     (2, 1), (4, 1)) for b in m["buckets"])


def test_engine_cache_parity_and_hit_rate():
    """Cache-on scores == cache-off scores; a repeated Zipfian stream hits."""
    cfg = _cfg()
    params = quantize_params(dlrm_init(jax.random.PRNGKey(0), cfg))
    reqs = _requests(16, seed=1) * 2  # repeat -> guaranteed reuse
    eng_c = RecsysEngine(cfg, params, max_batch=8,
                         cache=HotRowCache(capacity_rows=1024))
    eng_n = RecsysEngine(cfg, params, max_batch=8)
    for d, b in reqs:
        eng_c.submit(d, b)
        eng_n.submit(d, b)
    done_c = eng_c.run_until_drained()
    done_n = eng_n.run_until_drained()
    for uid in done_n:
        assert abs(done_c[uid].score - done_n[uid].score) < 1e-4
    stats = eng_c.metrics()["cache"]
    assert stats["hit_rate"] > 0 and stats["hits"] > 0
    assert stats["bytes_cached"] > 0


def test_engine_quantized_close_to_f32_and_dcn():
    cfg = _cfg()
    params = dlrm_init(jax.random.PRNGKey(0), cfg)
    qp = quantize_params(params)
    reqs = _requests(8, seed=2)
    scores = {}
    for tag, p in (("f32", params), ("int8", qp)):
        eng = RecsysEngine(cfg, p, max_batch=8)
        uids = [eng.submit(d, b) for d, b in reqs]
        done = eng.run_until_drained()
        scores[tag] = [done[u].score for u in uids]
    np.testing.assert_allclose(scores["int8"], scores["f32"], atol=5e-2)

    dcfg = DCNConfig(table_sizes=SIZES, emb_dim=16, cross_layers=2,
                     deep_mlp=(32, 16),
                     embedding=EmbeddingSpec(kind="qr", num_collisions=4,
                                             threshold=40))
    dparams = dcn_init(jax.random.PRNGKey(1), dcfg)
    eng = RecsysEngine(dcfg, quantize_params(dparams), max_batch=8,
                       cache=HotRowCache())
    uids = [eng.submit(d, b) for d, b in reqs]
    assert len(eng.run_until_drained()) == len(uids)


def test_engine_validates_requests():
    cfg = _cfg()
    params = dlrm_init(jax.random.PRNGKey(0), cfg)
    eng = RecsysEngine(cfg, params)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(13), [[1], [2]])          # wrong feature count
    with pytest.raises(NotImplementedError):
        RecsysEngine(_cfg(embedding=EmbeddingSpec(kind="feature")), params)


def _oracle_score(params, cfg, dense, bags):
    """Direct per-request jnp forward at exact shapes (empty bags padded
    to one masked slot)."""
    lmax = max([len(b) for b in bags] + [1])
    idx = np.zeros((1, len(bags), lmax), np.int32)
    mask = np.zeros((1, len(bags), lmax), np.float32)
    for i, bag in enumerate(bags):
        idx[0, i, :len(bag)] = bag
        mask[0, i, :len(bag)] = 1.0
    return float(dlrm_forward(params, jnp.asarray(dense[None], jnp.float32),
                              jnp.asarray(idx), cfg,
                              mask=jnp.asarray(mask))[0])


def test_engine_empty_bags_match_oracle():
    """Empty multi-hot bags are legal Criteo traffic: the pooled feature
    must be the exact zero vector, end to end — mixed empty/non-empty
    bags through the engine == the jnp oracle, quantized tables and the
    hot-row cache both on (the acceptance path) and off."""
    cfg = _cfg()
    params = quantize_params(dlrm_init(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(3)
    reqs = []
    for _ in range(10):
        bags = [list(rng.integers(0, s, int(rng.integers(0, 3))))
                for s in SIZES]           # 0 => empty bag
        reqs.append((rng.normal(size=13), bags))
    reqs.append((rng.normal(size=13), [[] for _ in SIZES]))  # all empty
    reqs.append((rng.normal(size=13), [[1], [], [2]]))
    for cache in (None, HotRowCache(capacity_rows=256)):
        eng = RecsysEngine(cfg, params, max_batch=4, cache=cache)
        uids = [eng.submit(d, b) for d, b in reqs]
        done = eng.run_until_drained()
        for uid, (dense, bags) in zip(uids, reqs):
            want = _oracle_score(params, cfg, dense, bags)
            assert abs(done[uid].score - want) < 1e-4, (uid, cache)


def test_engine_all_empty_wave():
    """A whole wave of all-empty requests (the `max()`-over-empty-bags
    hardening in `_pad_wave`) serves, and its features are exactly the
    zero vectors — scores equal the oracle's zero-feature forward."""
    cfg = _cfg()
    params = dlrm_init(jax.random.PRNGKey(0), cfg)
    eng = RecsysEngine(cfg, params, max_batch=4)
    rng = np.random.default_rng(4)
    reqs = [(rng.normal(size=13), [[] for _ in SIZES]) for _ in range(5)]
    uids = [eng.submit(d, b) for d, b in reqs]
    done = eng.run_until_drained()
    for uid, (dense, bags) in zip(uids, reqs):
        want = _oracle_score(params, cfg, dense, bags)
        assert abs(done[uid].score - want) < 1e-5
    assert all(b[1] == 1 for b in eng.metrics()["buckets"])  # Lb floor = 1


def test_engine_inference_placement_smoke():
    """params placed under INFERENCE_OVERRIDES (mesh path) still serve."""
    cfg = _cfg()
    params = quantize_params(dlrm_init(jax.random.PRNGKey(0), cfg))
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = RecsysEngine(cfg, params, max_batch=4, mesh=mesh)
    uid = eng.submit(np.zeros(13), [[1], [2, 3], [4]])
    done = eng.run_until_drained()
    assert np.isfinite(done[uid].score)


# ------------------------------------------------- quantized model end-to-end


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_quantized_dlrm_loss_close(mode):
    from repro.data.criteo import CriteoSpec, batch_at
    cfg = _cfg()
    spec = CriteoSpec(table_sizes=SIZES)
    params = dlrm_init(jax.random.PRNGKey(0), cfg)
    batch = batch_at(0, 0, 128, spec)
    base = float(dlrm_loss_fn(params, batch, cfg)[0])
    q = float(dlrm_loss_fn(quantize_params(params, mode=mode), batch, cfg)[0])
    assert abs(base - q) < 0.05, (base, q)


def test_quantized_dlrm_kernel_path_matches_ref_path():
    """use_kernel=True routes quantized QR pairs through the fused Pallas
    kernel; scores must match the jnp dequant path."""
    from repro.data.criteo import CriteoSpec, batch_at
    spec = CriteoSpec(table_sizes=SIZES)
    batch = batch_at(0, 3, 32, spec)
    cfg_k = _cfg(use_kernel=True)
    cfg_r = _cfg(use_kernel=False)
    params = dlrm_init(jax.random.PRNGKey(0), cfg_r)
    qp = quantize_params(params)
    got = dlrm_forward(qp, batch["dense"], batch["sparse"], cfg_k)
    want = dlrm_forward(qp, batch["dense"], batch["sparse"], cfg_r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
