"""The serving hot path, proven: differential grid for the fused
gather→dequant→pool→project kernel against the jnp oracle, hypothesis
property tests for the device-resident hot-row cache, and a pinned
512-request golden trace showing the continuous-batching engine is
bit-identical to the oracle pipeline with the cache on and off."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import EmbeddingSpec
from repro.kernels import ops, ref
from repro.kernels.serve_path import fused_serve_pool
from repro.models.dlrm import DLRMConfig, dlrm_forward, dlrm_init
from repro.plan import (build_plan, dim_ladder, full_table_bytes,
                        power_law_stats)
from repro.serve.cache import CachePinned, DeviceHotRowCache, HotRowCache
from repro.serve.quantize import quantize_params, quantize_table
from repro.serve.recsys import RecsysEngine

SIZES = (100, 500, 33, 2000)
DIM = 16

# ------------------------------------------------------------------ helpers


def _meta(q):
    return jnp.concatenate([q["scale"].astype(jnp.float32),
                            q["zp"].astype(jnp.float32)], axis=1)


def _tables(key, rows_a, rows_b, d, mode):
    """(w_a, w_b, meta_a, meta_b) in the requested serving mode."""
    ka, kb = jax.random.split(key)
    wa = jax.random.normal(ka, (rows_a, d), jnp.float32)
    wb = jax.random.normal(kb, (rows_b, d), jnp.float32)
    if mode == "int8":
        qa, qb = quantize_table(wa), quantize_table(wb)
        return qa["q"], qb["q"], _meta(qa), _meta(qb)
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    return wa.astype(dt), wb.astype(dt), None, None


def _bags(key, b, l, hi):
    """(idx, mask) with one fully-empty bag row (row b-1) whenever b > 1."""
    ki, km = jax.random.split(key)
    idx = jax.random.randint(ki, (b, l), 0, hi)
    mask = (jax.random.uniform(km, (b, l)) > 0.3).astype(jnp.float32)
    if b > 1 and l > 0:
        mask = mask.at[b - 1].set(0.0)     # empty bag pools to exact zero
    return idx, mask


def _tol(mode):
    # one f32 accumulation-order difference is allowed between the kernel's
    # sequential bag sum and the oracle's axis reduction; bf16 outputs round
    # once to bf16 so the bound widens to its eps
    return {"f32": 2e-5, "int8": 2e-5, "bf16": 2e-2}[mode]


# ------------------------------------------------- tentpole differential grid


@pytest.mark.parametrize("mode", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("l", [0, 1, 7, 16])
def test_fused_kernel_matches_oracle_grid(mode, l):
    """{f32, bf16, int8} × L ∈ {0, 1, 7, 16} × D ∈ {16, 64, 128} ×
    {uniform, mixed-width} — kernel (interpret) vs ``kernels.ref`` oracle,
    QR pair and pre-folded single table, empty bags included (L=0 is the
    all-empty wave: the wrapper pads to one masked slot)."""
    b, m = 3, 10
    for cell, d_out in enumerate((16, 64, 128)):
        for mixed in (False, True):
            d = d_out // 2 if mixed else d_out
            key = jax.random.PRNGKey(17 * cell + mixed)
            wa, wb, ma, mb = _tables(key, m, 5, d, mode)
            proj = jax.random.normal(jax.random.fold_in(key, 3),
                                     (d, d_out)) if mixed else None
            idx, mask = _bags(jax.random.fold_in(key, 4), b, l, m * 5)
            pairs = [dict(idx_a=idx % m, idx_b=idx // m, w_b=wb,
                          meta_b=mb)]
            if d_out == 16:   # single-table (full/hash) variant of the cell
                pairs.append(dict(idx_a=idx % m))
            for kw in pairs:
                got = fused_serve_pool(mask=mask, w_a=wa, meta_a=ma,
                                       proj=proj, op="mult", interpret=True,
                                       **kw)
                want = ref.fused_serve_pool_ref(mask=mask, w_a=wa,
                                                meta_a=ma, proj=proj,
                                                op="mult", **kw)
                assert got.shape == want.shape and got.dtype == want.dtype
                np.testing.assert_allclose(
                    np.asarray(got, np.float32),
                    np.asarray(want, np.float32),
                    rtol=_tol(mode), atol=_tol(mode),
                    err_msg=f"{mode} L={l} D={d_out} mixed={mixed}")
                # the empty bag row pools (and projects) to exact zero
                if b > 1:
                    np.testing.assert_array_equal(
                        np.asarray(got)[b - 1], 0.0)


def test_fused_kernel_add_op_and_validation():
    wa, wb, ma, mb = _tables(jax.random.PRNGKey(0), 8, 4, 16, "int8")
    idx, mask = _bags(jax.random.PRNGKey(1), 2, 5, 32)
    got = fused_serve_pool(idx % 8, mask, wa, idx_b=idx // 8, w_b=wb,
                           meta_a=ma, meta_b=mb, op="add", interpret=True)
    want = ref.fused_serve_pool_ref(idx % 8, mask, wa, idx_b=idx // 8,
                                    w_b=wb, meta_a=ma, meta_b=mb, op="add")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="pairs"):
        fused_serve_pool(idx % 8, mask, wa, idx_b=idx // 8, w_b=None,
                         interpret=True)
    with pytest.raises(ValueError, match="pairs"):
        fused_serve_pool(idx % 8, mask, wa, idx_b=idx // 8, w_b=wb,
                         meta_a=ma, meta_b=None, interpret=True)


def test_serve_bag_pool_routing():
    """ops.serve_bag_pool: kernel path == oracle path == the unfusable
    fallbacks (concat, mixed dense+quant pair) on the same contract."""
    key = jax.random.PRNGKey(2)
    wa = jax.random.normal(key, (12, 8))
    wb = jax.random.normal(jax.random.fold_in(key, 1), (4, 8))
    qa, qb = quantize_table(wa), quantize_table(wb)
    proj = jax.random.normal(jax.random.fold_in(key, 2), (8, 16))
    idx = jax.random.randint(jax.random.fold_in(key, 3), (3, 6), 0, 48)
    mask = (jax.random.uniform(jax.random.fold_in(key, 4), (3, 6)) > 0.4
            ).astype(jnp.float32)
    for args in ((idx, mask, qa, qb), (idx, mask, wa, wb),
                 (idx % 12, mask, qa, None)):
        got = ops.serve_bag_pool(*args, proj=proj)
        want = ops.serve_bag_pool(*args, proj=proj, use_kernel=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    # concat pair: jnp fallback, widths concatenate before the projection
    pc = jax.random.normal(jax.random.fold_in(key, 5), (16, 16))
    out = ops.serve_bag_pool(idx, mask, wa, wb, op="concat", proj=pc)
    rows = jnp.concatenate([jnp.take(wa, idx % 12, axis=0),
                            jnp.take(wb, idx // 12, axis=0)], axis=-1)
    pooled = (rows * mask[..., None]).sum(axis=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(pooled @ pc),
                               rtol=1e-5, atol=1e-5)
    # mixed dense+quant pair is not fusable; still matches the contract
    got = ops.serve_bag_pool(idx, mask, qa, wb)
    a = (jnp.take(qa["q"], idx % 12, axis=0).astype(jnp.float32)
         - qa["zp"][idx % 12]) * qa["scale"][idx % 12]
    b = jnp.take(wb, idx // 12, axis=0)
    want = ((a * b) * mask[..., None]).sum(axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------- device cache property harness


def _zipf_stream(seed, n, universe=40):
    rng = np.random.default_rng(seed)
    return [int(k) % universe for k in rng.zipf(1.3, size=n)]


@settings(max_examples=20, deadline=None)
@given(st.sampled_from(["lru", "lfu"]), st.integers(1, 12),
       st.integers(0, 10_000))
def test_device_cache_capacity_and_conservation(policy, cap, seed):
    """Row capacity never exceeded; insertions − evictions − invalidations
    always equals the resident count; every resident row reads back as the
    exact value admitted."""
    c = DeviceHotRowCache(capacity_rows=cap, policy=policy)
    for k in _zipf_stream(seed, 150):
        if c.get(k) is None:
            c.put(k, np.full(8, float(k) + 0.5, np.float32))
        assert len(c) <= cap
    s = c.stats
    assert s.insertions - s.evictions - s.invalidations == len(c)
    assert s.hits + s.misses == 150
    for k in list(c._rows):
        np.testing.assert_array_equal(
            c.get(k), np.full(8, float(k) + 0.5, np.float32))


@settings(max_examples=15, deadline=None)
@given(st.sampled_from(["lru", "lfu"]), st.integers(64, 600),
       st.integers(0, 10_000))
def test_device_cache_byte_budget_mixed_widths(policy, cap_bytes, seed):
    """Byte budget never exceeded with mixed-width rows (the mixed-dim
    serving shape); oversized rows reject instead of flushing."""
    c = DeviceHotRowCache(capacity_rows=None, capacity_bytes=cap_bytes,
                          policy=policy)
    for k in _zipf_stream(seed, 120):
        width = 4 * (1 + k % 4)            # 4/8/12/16 f32 → 16..64 bytes
        if c.get(k) is None:
            c.put(k, np.full(width, float(k), np.float32))
        assert c.stats.bytes_cached <= cap_bytes
    assert c.stats.bytes_cached == sum(r.nbytes for r in c._rows.values())


@settings(max_examples=15, deadline=None)
@given(st.sampled_from(["lru", "lfu"]), st.integers(2, 10),
       st.integers(0, 10_000))
def test_device_replay_bit_exact_and_matches_host(policy, cap, seed):
    """replay() is reproducible bit-exactly on a fresh device cache, and
    the device cache's event log + stats are identical to the host
    cache's for the same stream — storage residency must not leak into
    policy behaviour."""
    stream = _zipf_stream(seed, 100)
    logs, stats = [], []
    for cls in (HotRowCache, DeviceHotRowCache, DeviceHotRowCache):
        c = cls(capacity_rows=cap, policy=policy)
        logs.append(c.replay(stream, row_bytes=32))
        stats.append(c.stats.as_dict())
    assert logs[0] == logs[1] == logs[2]
    assert stats[0] == stats[1] == stats[2]


def test_device_cache_pinning_blocks_eviction():
    """put_many never evicts a pinned key: admission is rejected instead
    (the engine's same-wave slot-integrity guarantee)."""
    c = DeviceHotRowCache(capacity_rows=2)
    c.put_many([1, 2], np.ones((2, 4), np.float32))
    admitted = c.put_many([3], np.zeros((1, 4), np.float32), pinned=[1, 2])
    assert admitted == [] and c.stats.rejections == 1
    assert sorted(c._rows) == [1, 2]
    with pytest.raises(CachePinned):
        c._pinned = {1, 2}
        try:
            c._victim()
        finally:
            c._pinned = set()
    # unpinned, the same admission lands and evicts per policy
    assert c.put_many([3], np.zeros((1, 4), np.float32)) == [3]
    assert c.stats.evictions == 1


def test_device_cache_scatter_dedupes_reused_slot():
    """A slot freed by an eviction and reused in the same put_many wave
    must land the *newer* row (last-write-wins in the batched scatter)."""
    c = DeviceHotRowCache(capacity_rows=1)
    rows = np.stack([np.full(4, 1.0, np.float32),
                     np.full(4, 2.0, np.float32)])
    c.put_many([10, 11], rows)          # 10 admitted then evicted for 11
    assert list(c._rows) == [11]
    np.testing.assert_array_equal(c.get(11), rows[1])


def test_device_cache_lookup_many_counts_occurrences():
    c = DeviceHotRowCache(capacity_rows=8)
    c.put(5, np.ones(4, np.float32))
    slots, miss = c.lookup_many([5, 6], counts=np.array([3, 2]))
    assert slots[0] >= 0 and slots[1] == -1
    assert (miss == [False, True]).all()
    assert c.stats.hits == 3 and c.stats.misses == 2


# ----------------------------------------------- golden 512-request trace

TRACE_N = 512
# Pinned behavioural goldens for the recorded trace (floats are asserted
# by bit-identity *between* pipelines, never against literals):
GOLDEN_WAVES = 22
GOLDEN_BUCKETS = [(2, 1), (8, 2), (16, 1), (16, 2), (16, 4), (16, 8),
                  (32, 4), (32, 8)]
GOLDEN_CACHE = {"hits": 4072, "misses": 882, "evictions": 0,
                "insertions": 670, "rejections": 0, "invalidations": 0,
                "bytes_cached": 15184, "lookups": 4954,
                "hit_rate": 0.8219620508679855}
GOLDEN_EVENTS_SHA1 = "9b94b32e3db8749960d166043838d7d689f67568"


def _mixed_plan(frac=0.25):
    stats = [power_law_stats(n, alpha=1.2) for n in SIZES]
    return build_plan(stats, DIM, int(full_table_bytes(SIZES, DIM) * frac),
                      dims=dim_ladder(DIM), arch="serve-path-golden")


def _trace(n=TRACE_N, seed=11):
    rng = np.random.default_rng(seed)
    reqs = []
    for r in range(n):
        if r % 16 == 15:
            bags = [[] for _ in SIZES]             # all-empty request
        else:
            bags = [list((rng.zipf(1.3, size=int(rng.integers(0, 6)))
                          - 1) % s) for s in SIZES]
        reqs.append((rng.normal(size=13), bags))
    return reqs


class _RecordingEngine(RecsysEngine):
    """RecsysEngine that records every padded wave (the oracle replays
    the exact shapes the engine served)."""

    def _pad_wave(self, wave):
        out = super()._pad_wave(wave)
        self.trace = getattr(self, "trace", [])
        self.trace.append((out, [r.uid for r in wave]))
        return out


def test_golden_trace_engine_bit_identical_to_oracle():
    """The tentpole acceptance: over a recorded 512-request mixed-plan
    trace (quantized tables, empty bags, Zipf ids), the
    continuous-batching engine's scores are **bit-identical** across
    cache off / device cache / host cache, and bit-identical to the jnp
    oracle (one jitted ``dlrm_forward`` per recorded wave shape).  Wave
    formation, bucket set, device-cache counters, and the cache event log
    are pinned as goldens — any behavioural drift in batching, admission,
    or eviction shows up here before it shows up in production."""
    plan = _mixed_plan()
    cfg = DLRMConfig(table_sizes=SIZES, emb_dim=DIM, bottom_mlp=(32, 16),
                     top_mlp=(32,), embedding=plan)
    qp = quantize_params(dlrm_init(jax.random.PRNGKey(0), cfg))
    reqs = _trace()

    def run(cache):
        eng = _RecordingEngine(cfg, qp, max_batch=32, cache=cache)
        uids = [eng.submit(d, b) for d, b in reqs]
        done = eng.run_until_drained()
        return np.array([done[u].score for u in uids], np.float32), eng

    dev_cache = DeviceHotRowCache(capacity_rows=4096, record_events=True)
    s_off, eng_off = run(None)
    s_dev, eng_dev = run(dev_cache)
    s_host, _ = run(HotRowCache(capacity_rows=4096))
    np.testing.assert_array_equal(s_dev, s_off)
    # host cache pools/projects in numpy (compat path): its projection
    # matmul may differ from XLA's by 1 ulp on mixed-dim plans
    np.testing.assert_allclose(s_host, s_off, rtol=1e-6, atol=1e-6)

    # oracle: one jitted full forward per recorded wave shape
    oracle = jax.jit(lambda p, d, i, m: dlrm_forward(p, d, i, cfg, mask=m))
    want = {}
    for (dense, idx, mask), uids in eng_dev.trace:
        logits = np.asarray(oracle(qp, jnp.asarray(dense), jnp.asarray(idx),
                                   jnp.asarray(mask)), np.float32)
        for b, uid in enumerate(uids):
            want[uid] = logits[b]
    np.testing.assert_array_equal(
        s_dev, np.array([want[u] for u in range(len(reqs))], np.float32))

    # pinned behavioural goldens
    m = eng_dev.metrics()
    assert m["waves"] == GOLDEN_WAVES, m["waves"]
    assert m["buckets"] == GOLDEN_BUCKETS, m["buckets"]
    assert eng_off.metrics()["waves"] == GOLDEN_WAVES
    assert m["cache"] == GOLDEN_CACHE, m["cache"]
    sha = hashlib.sha1(repr(dev_cache.events).encode()).hexdigest()
    assert sha == GOLDEN_EVENTS_SHA1, sha


def test_tiny_cache_falls_back_bit_identical():
    """A cache smaller than one wave's working set rejects admission and
    serves in-graph — still bit-identical, only slower."""
    cfg = DLRMConfig(table_sizes=SIZES[:3], emb_dim=DIM, bottom_mlp=(32, 16),
                     top_mlp=(32,),
                     embedding=EmbeddingSpec(kind="qr", num_collisions=4,
                                             threshold=40))
    qp = quantize_params(dlrm_init(jax.random.PRNGKey(0), cfg))
    reqs = _trace(48, seed=5)
    reqs = [(d, b[:3]) for d, b in reqs]

    def run(cache):
        eng = RecsysEngine(cfg, qp, max_batch=8, cache=cache)
        uids = [eng.submit(d, b) for d, b in reqs]
        done = eng.run_until_drained()
        return np.array([done[u].score for u in uids], np.float32)

    s_off = run(None)
    tiny = DeviceHotRowCache(capacity_rows=2)
    np.testing.assert_array_equal(run(tiny), s_off)
    assert tiny.stats.rejections > 0


def test_continuous_batching_groups_by_bucket_and_serves_head_first():
    """Wave formation: same-bucket requests coalesce (no pow2 cross-bucket
    padding), and the queue head always anchors the next wave — a long-bag
    head cannot be starved by a run of short requests behind it."""
    cfg = DLRMConfig(table_sizes=SIZES[:2], emb_dim=DIM, bottom_mlp=(16,),
                     top_mlp=(16,),
                     embedding=EmbeddingSpec(kind="qr", num_collisions=4,
                                             threshold=1000))
    params = dlrm_init(jax.random.PRNGKey(0), cfg)
    # max_inflight=0: reap synchronously so each step returns its own wave
    eng = RecsysEngine(cfg, params, max_batch=4, max_inflight=0)
    long_uid = eng.submit(np.zeros(13), [[1] * 9, [2] * 9])      # bucket 16
    for k in range(6):
        eng.submit(np.zeros(13), [[k], [k]])                     # bucket 1
    first = eng.step()
    assert [r.uid for r in first] == [long_uid]    # head anchors, ships alone
    eng.run_until_drained()
    assert set(eng.metrics()["buckets"]) == {(1, 16), (4, 1), (2, 1)}

    # legacy mode: strict FIFO slices (one mixed wave padded to (4, 16))
    eng_w = RecsysEngine(cfg, params, max_batch=4, batching="waves")
    eng_w.submit(np.zeros(13), [[1] * 9, [2] * 9])
    for k in range(3):
        eng_w.submit(np.zeros(13), [[k], [k]])
    eng_w.run_until_drained()
    assert eng_w.metrics()["buckets"] == [(4, 16)]


def test_engine_rejects_unknown_batching_mode():
    cfg = DLRMConfig(table_sizes=SIZES[:2], emb_dim=DIM,
                     embedding=EmbeddingSpec(kind="qr", num_collisions=4))
    params = dlrm_init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="batching"):
        RecsysEngine(cfg, params, batching="nope")
