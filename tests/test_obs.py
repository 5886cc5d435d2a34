"""repro.obs: registry semantics, tracer/Chrome-trace export, engine
stage timelines, collision telemetry, and the read-only contract
(obs-on must not change a single score)."""

import json
import time

import jax
import numpy as np
import pytest

from repro.core import EmbeddingSpec
from repro.data.criteo import CriteoSpec, batch_at
from repro.models.dlrm import DLRMConfig, dlrm_init, dlrm_loss_fn, tables_for
from repro.obs import CollisionTelemetry, MetricsRegistry, Obs, Tracer
from repro.obs.collision import predicted_collision_mass
from repro.optim.optimizers import adagrad
from repro.plan.freq import FeatureStats
from repro.serve.cache import HotRowCache
from repro.serve.quantize import quantize_params
from repro.serve import recsys
from repro.serve.recsys import (HOST_SPANS, STAGE_PARTITION, STAGES,
                                RecsysEngine)
from repro.train.loop import TrainConfig, Trainer, init_state, make_train_step

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


# ------------------------------------------------------------------ registry


def test_registry_get_or_create_and_type_clash():
    reg = MetricsRegistry()
    c = reg.counter("requests", "help text")
    assert reg.counter("requests") is c
    with pytest.raises(TypeError):
        reg.gauge("requests")
    with pytest.raises(TypeError):
        reg.histogram("requests")


def test_counter_and_gauge_label_semantics():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    c.inc(2, stage="a")
    c.inc(3, stage="a")
    c.inc(5, stage="b")
    assert c.value(stage="a") == 5
    assert c.value(stage="b") == 5
    # label order must not matter: one series per label *set*
    h1 = c.labels(x="1", y="2")
    h2 = c.labels(y="2", x="1")
    assert h1 is h2
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(7, q="main")
    g.set(3, q="main")
    assert g.value(q="main") == 3


def test_histogram_percentiles_match_numpy():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    rng = np.random.default_rng(0)
    samples = rng.lognormal(size=257)
    for s in samples:
        h.observe(float(s))
    for q in (0, 10, 50, 90, 99, 100):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(samples, q)), rel=1e-12)
    summ = h.labels().summary()
    assert summ["count"] == len(samples)
    assert summ["sum"] == pytest.approx(float(samples.sum()))
    assert summ["p99"] == pytest.approx(float(np.percentile(samples, 99)))
    with pytest.raises(ValueError):
        reg.histogram("empty").percentile(50)
    with pytest.raises(ValueError):
        h.percentile(101)


def test_histogram_bounded_samples_drop_oldest():
    reg = MetricsRegistry()
    h = reg.histogram("b", max_samples=4)
    for v in range(10):
        h.observe(float(v))
    s = h.labels()
    assert s.samples == [6.0, 7.0, 8.0, 9.0]
    assert s.count == 10          # count/sum keep the full traffic
    assert s.sum == float(sum(range(10)))


def test_registry_merge_semantics():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("c").inc(1, k="x")
    b.counter("c").inc(2, k="x")
    b.counter("c").inc(7, k="y")
    a.gauge("g").set(1)
    b.gauge("g").set(9)
    a.histogram("h").observe(1.0)
    b.histogram("h").observe(3.0)
    a.merge(b)
    assert a.counter("c").value(k="x") == 3      # counters sum
    assert a.counter("c").value(k="y") == 7
    assert a.gauge("g").value() == 9             # gauge: other wins
    s = a.histogram("h").labels()
    assert sorted(s.samples) == [1.0, 3.0]       # histograms union
    assert s.count == 2 and s.sum == 4.0


def test_registry_reset_keeps_bound_handles_live():
    reg = MetricsRegistry()
    c = reg.counter("serve_requests").labels()
    h = reg.histogram("serve_lat").labels()
    other = reg.counter("train_steps").labels()
    c.inc(5)
    h.observe(1.0)
    other.inc(2)
    reg.reset(prefix="serve_")
    assert c.value == 0 and h.count == 0 and h.samples == []
    assert other.value == 2                      # prefix respected
    c.inc(1)                                     # old handle still works
    assert reg.counter("serve_requests").value() == 1


def test_registry_jsonl_round_trip():
    reg = MetricsRegistry()
    reg.counter("c").inc(3, k="x")
    reg.histogram("h").observe(2.0)
    recs = [json.loads(line) for line in reg.to_jsonl().splitlines()]
    by_name = {r["name"]: r for r in recs}
    assert by_name["c"]["type"] == "counter"
    assert by_name["c"]["value"] == 3
    assert by_name["c"]["labels"] == {"k": "x"}
    assert by_name["h"]["type"] == "histogram"
    assert by_name["h"]["count"] == 1


# ------------------------------------------------------------------- tracer


def test_tracer_nesting_and_chrome_trace_round_trip():
    tr = Tracer()
    with tr.span("outer", kind="t"):
        with tr.span("inner"):
            pass
    tr.complete("mark", tr._t0, 0.0, cat="interval", k=1)
    payload = json.loads(tr.to_json())
    evs = payload["traceEvents"]
    by_name = {e["name"]: e for e in evs}
    # inner closes (and records) before outer
    assert [e["name"] for e in evs] == ["inner", "outer", "mark"]
    assert by_name["outer"]["args"]["depth"] == 0
    assert by_name["inner"]["args"]["depth"] == 1
    assert by_name["mark"]["args"] == {"k": 1}
    assert [e["cat"] for e in evs] == ["host", "host", "interval"]
    for e in evs:
        assert e["ph"] == "X" and e["ts"] >= 0 and e["dur"] >= 0
    # inner nests inside outer on the chrome timeline
    o, i = by_name["outer"], by_name["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3
    assert len(tr) == 3
    assert len(tr.drain()) == 3 and len(tr) == 0
    tr = Tracer(max_events=2)                     # bounded: oldest dropped
    for k in range(5):
        tr.complete(f"e{k}", 0.0, 0.0)
    assert [e["name"] for e in tr.drain()] == ["e3", "e4"]


# ------------------------------------------------------- collision telemetry


def test_collision_measured_equals_predicted_on_same_distribution():
    """Same estimator, same distribution -> the measured and predicted
    collision masses must agree exactly (the bench's table compares the
    two under *different* distributions; here we pin the estimators)."""
    # hash tables (lossy by construction): ids 0 and m share a bucket,
    # so the collision mass is deterministically nonzero
    cfg = _cfg(embedding=EmbeddingSpec(kind="hash", num_collisions=4,
                                       threshold=40))
    mods = tables_for(cfg)
    m = mods[1].m
    assert 1 < m < SIZES[1]
    ct = CollisionTelemetry(SIZES, compact_every=2)
    ids = np.array([0, m, 0, m, 1])
    idx = np.zeros((5, 3, 1), np.int64)
    idx[:, 1, 0] = ids
    mask = np.zeros((5, 3, 1), np.int32)
    mask[:, 1, 0] = 1
    ct.record(idx, mask)
    assert ct.observed_lookups(1) == 5
    assert ct.observed_support(1) == 3
    assert ct.observed_lookups(0) == 0            # masked features drop out
    measured = ct.measured_collision_mass(mods[1], 1)
    assert measured > 0 and np.isfinite(measured)
    st = ct.observed_stats(1)
    assert st.ids.tolist() == [0, 1, m]
    assert st.probs.tolist() == [0.4, 0.2, 0.4]
    predicted = predicted_collision_mass(mods[1], st)
    assert measured == pytest.approx(predicted)
    # drifted stats -> the comparison moves (the signal the table exists
    # for): ids 0 and 1 land in distinct hash buckets, zero collision mass
    drifted = FeatureStats(size=SIZES[1], ids=np.array([0, 1]),
                           probs=np.array([0.5, 0.5]))
    assert predicted_collision_mass(mods[1], drifted) == 0.0
    assert measured != pytest.approx(0.0)


def test_collision_live_rows_trim_and_report():
    ct = CollisionTelemetry(SIZES, compact_every=64)
    idx = np.ones((4, 3, 2), np.int64)
    ct.record(idx, np.ones((4, 3, 2), np.int32), live_rows=2)
    assert ct.observed_lookups(0) == 4            # 2 live rows x bag of 2
    assert ct.requests == 2 and ct.waves == 1
    rows = ct.report(tables_for(_cfg()))
    assert [r["feature"] for r in rows] == [0, 1, 2]
    assert all(r["observed_support"] == 1 for r in rows)
    assert all(np.isfinite(r["measured_collision_mass"]) for r in rows)


# -------------------------------------------------------------- engine obs


def test_engine_stage_partition_sums_to_latency():
    cfg = _cfg()
    qp = quantize_params(dlrm_init(jax.random.PRNGKey(0), cfg))
    for batching in ("continuous", "waves"):
        obs = Obs(trace=True, collisions=True)
        eng = RecsysEngine(cfg, qp, max_batch=4,
                           cache=HotRowCache(capacity_rows=512),
                           batching=batching, obs=obs)
        reqs = _requests(13, seed=3)
        uids = [eng.submit(d, b) for d, b in reqs]
        done = eng.run_until_drained()
        assert len(done) == len(uids)
        ss = eng.stage_summary()
        assert set(STAGES) <= set(ss)
        # the five partition stages tile [t0, t1]: ratio 1 by construction
        assert ss["partition"]["ratio"] == pytest.approx(1.0, abs=1e-9)
        assert ss["partition"]["latency_sum_s"] > 0
        waves = ss["probe"]["count"]
        assert waves > 0
        assert all(ss[s]["count"] == waves for s in STAGE_PARTITION)
        assert obs.registry.counter("serve_requests_total").value() \
            == len(reqs)
        # one wave bar, its queue wait and time in flight, and the eight
        # host spans per wave
        names = [e["name"] for e in obs.tracer.events]
        assert names.count("wave") == waves
        for s in ("queue_wait", "inflight") + HOST_SPANS:
            assert names.count(s) == waves
        assert obs.collisions is not None and obs.collisions.waves == waves


def test_engine_obs_zero_requests_and_all_empty_bags():
    cfg = _cfg()
    qp = quantize_params(dlrm_init(jax.random.PRNGKey(0), cfg))
    obs = Obs(trace=True, collisions=True)
    eng = RecsysEngine(cfg, qp, max_batch=4, obs=obs)
    # zero traffic: summaries exist, ratio degrades to 1.0, nothing raises
    ss = eng.stage_summary()
    assert ss["partition"]["ratio"] == 1.0
    assert ss["probe"]["count"] == 0
    assert eng.run_until_drained() == {}
    # all-empty-bag wave: every feature pools to the zero vector but the
    # wave still flows through every stage of the timeline
    rng = np.random.default_rng(0)
    uids = [eng.submit(rng.normal(size=13), [[], [], []]) for _ in range(3)]
    done = eng.run_until_drained()
    assert all(np.isfinite(done[u].score) for u in uids)
    ss = eng.stage_summary()
    assert ss["probe"]["count"] > 0
    assert ss["partition"]["ratio"] == pytest.approx(1.0, abs=1e-9)
    assert obs.collisions.observed_lookups(0) == 0   # no live ids served


def test_engine_obs_is_read_only_bitwise():
    cfg = _cfg()
    qp = quantize_params(dlrm_init(jax.random.PRNGKey(0), cfg))
    reqs = _requests(17, seed=5) * 2
    eng_off = RecsysEngine(cfg, qp, max_batch=4,
                           cache=HotRowCache(capacity_rows=512))
    eng_on = RecsysEngine(cfg, qp, max_batch=4,
                          cache=HotRowCache(capacity_rows=512),
                          obs=Obs(trace=True, collisions=True))
    uids = [(eng_off.submit(d, b), eng_on.submit(d, b)) for d, b in reqs]
    done_off, done_on = eng_off.run_until_drained(), eng_on.run_until_drained()
    for a, b in uids:
        assert done_on[b].score == done_off[a].score


class _Clock:
    """``time`` for the engine module, counting ``monotonic`` reads."""

    def __init__(self):
        self.reads = 0

    def monotonic(self):
        self.reads += 1
        return time.monotonic()


@pytest.mark.parametrize("mode", ["off", "registry", "traced"])
def test_engine_host_spans_per_wave_and_clock_reads(mode, monkeypatch):
    """With the tracer on, each wave records the eight ``serve.*`` host
    spans, in order and back to back but for the wave's time in flight,
    at three clock reads a wave more than the registry alone; off, the
    engine records nothing and reads the clock twice a wave (its own
    latency), as before spans existed."""
    cfg = _cfg()
    qp = quantize_params(dlrm_init(jax.random.PRNGKey(0), cfg))
    obs = {"off": None, "registry": Obs(),
           "traced": Obs(trace=True)}[mode]
    eng = RecsysEngine(cfg, qp, max_batch=4, obs=obs)
    reqs = _requests(4, seed=11)
    for d, b in reqs:                       # compile the bucket first
        eng.submit(d, b)
    eng.run_until_drained()
    if obs is not None and obs.tracer is not None:
        obs.tracer.drain()
    clock = _Clock()
    monkeypatch.setattr(recsys, "time", clock)
    for d, b in reqs:
        eng.submit(d, b)
    submits, clock.reads = clock.reads, 0
    steps = 0
    while eng._queue or eng._inflight:
        eng.step()
        steps += 1
    assert steps == 1                       # one wave of four
    per_wave = {"off": 2, "registry": 7, "traced": 10}[mode]
    assert clock.reads == per_wave
    assert submits == (0 if obs is None else len(reqs))
    if mode != "traced":
        assert obs is None or obs.tracer is None
        return
    evs = obs.tracer.drain()
    host = [e for e in evs if e["cat"] == "host"]
    assert [e["name"] for e in host] == list(HOST_SPANS)
    assert {e["name"] for e in evs if e["cat"] == "interval"} \
        == {"wave", "queue_wait", "inflight"}
    assert all(e["dur"] >= 0 for e in evs)
    ends = {e["name"]: e["ts"] + e["dur"] for e in evs}
    starts = {e["name"]: e["ts"] for e in evs}
    for a, b in zip(HOST_SPANS, HOST_SPANS[1:]):
        gap = starts[b] - ends[a]
        if a == "serve.dense":              # the wave in flight between
            assert gap == pytest.approx(ends["inflight"] - starts["inflight"])
        else:
            assert gap == pytest.approx(0.0, abs=1e-6)


def test_reset_metrics_resets_cache_counters_keeps_residency():
    """The PR-8 bugfix pin: reset_metrics() must drop cache *traffic*
    counters with the timing stats (so steady-state hit rates exclude the
    cold fill) while the resident rows — and their byte accounting —
    survive.  A replayed resident stream then hits at exactly 1.0."""
    cfg = _cfg()
    qp = quantize_params(dlrm_init(jax.random.PRNGKey(0), cfg))
    obs = Obs()
    eng = RecsysEngine(cfg, qp, max_batch=4,
                       cache=HotRowCache(capacity_rows=2048), obs=obs)
    reqs = _requests(16, seed=7)
    for d, b in reqs:
        eng.submit(d, b)
    eng.run_until_drained()
    st = eng.cache.stats
    assert st.lookups > 0 and st.misses > 0 and st.bytes_cached > 0
    resident = st.bytes_cached

    eng.reset_metrics()
    st = eng.cache.stats
    assert (st.hits, st.misses, st.lookups) == (0, 0, 0)
    assert st.bytes_cached == resident            # rows stayed resident
    assert eng.wave_latencies_s == []
    assert obs.registry.counter("serve_requests_total").value() == 0

    for d, b in reqs:                              # replay: fully resident
        eng.submit(d, b)
    eng.run_until_drained()
    m = eng.metrics()
    assert m["cache"]["hit_rate"] == 1.0
    assert m["cache"]["misses"] == 0


# -------------------------------------------------------------- trainer obs


def test_trainer_obs_counters_and_wire_handles():
    spec = CriteoSpec(table_sizes=SIZES)
    cfg = _cfg()

    def loss_fn(p, b):
        return dlrm_loss_fn(p, b, cfg)

    opt = adagrad(1e-2)
    state = init_state(dlrm_init(jax.random.PRNGKey(0), cfg), opt)
    obs = Obs(trace=True)
    step_wire = {"per_leaf": [{"path": "tables/0", "mode": "int8",
                               "nelems": 100, "wire_bytes": 123.0}],
                 "total_bytes": 200.0}
    tr = Trainer(make_train_step(loss_fn, opt),
                 TrainConfig(num_steps=6, log_every=2),
                 batch_at=lambda s: batch_at(0, s, 16, spec),
                 obs=obs, step_wire=step_wire)
    tr.run(state)
    reg = obs.registry
    assert reg.counter("train_steps_total").value() == 6
    h = reg.histogram("train_step_seconds").labels()
    assert h.count == 6 and h.sum > 0
    wire = reg.counter("train_wire_bytes_total")
    assert wire.value(leaf="tables/0", mode="int8") == 6 * 123.0
    assert wire.value(leaf="_other", mode="aggregate") == 6 * 77.0
    steps = [e for e in obs.tracer.events if e["name"] == "train_step"]
    assert [e["args"]["step"] for e in steps] == list(range(6))


def test_trainer_step_records_dispatch_and_wait_bitwise():
    """``Trainer.step`` with the tracer on records ``train.dispatch`` then
    ``train.wait`` per step, and returns bitwise the state and loss that
    the bare jitted ``train_step`` gives; ``run`` adds ``train.batch``."""
    spec = CriteoSpec(table_sizes=SIZES)
    cfg = _cfg()
    opt = adagrad(1e-2)

    def state0():   # a Trainer consumes its state: a fresh one each use
        return init_state(dlrm_init(jax.random.PRNGKey(0), cfg), opt)

    batches = [batch_at(0, s, 16, spec) for s in range(3)]
    step = make_train_step(lambda p, b: dlrm_loss_fn(p, b, cfg), opt)
    obs = Obs(trace=True)
    on = Trainer(step, TrainConfig(num_steps=3), batch_at=batches.__getitem__,
                 obs=obs)
    off = Trainer(step, TrainConfig(num_steps=3), batch_at=batches.__getitem__)
    a, b = state0(), state0()
    for t in range(3):
        a, ma = on.step(a, batches[t])
        b, mb = off.train_step(b, batches[t])
        assert float(ma["loss"]) == float(mb["loss"])
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    evs = obs.tracer.drain()
    assert [e["name"] for e in evs] == ["train.dispatch", "train.wait"] * 3
    assert all(e["cat"] == "host" and e["dur"] >= 0 for e in evs)
    for d, w in zip(evs[::2], evs[1::2]):   # the wait follows the dispatch
        assert w["ts"] >= d["ts"] + d["dur"] - 1e-6
    on.run(state0())
    names = [e["name"] for e in obs.tracer.drain()]
    assert names == ["train.batch", "train.dispatch", "train.wait",
                     "train_step"] * 3
