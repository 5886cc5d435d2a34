"""Continuous-batching recsys inference engine over quantized tables.

The LM path serves token waves (``serve.engine``); recommendation traffic
is different: each request is *one* scoring call carrying 13 dense floats
plus a variable-length multi-hot id bag per categorical feature.  The
engine:

* **queues** requests and forms waves by **continuous batching**
  (``batching="continuous"``, the default): the head request anchors the
  wave's bag-length bucket and up to ``max_batch`` same-bucket requests
  from a bounded lookahead window ride along, so one long-bag request no
  longer drags every short request into its padded shape.  The head always
  ships in the next wave — no starvation.  ``batching="waves"`` keeps the
  legacy lock-step FIFO slices (and their exact wave/bucket accounting,
  which the padding tests pin);
* **pads + buckets** every wave to a fixed shape — batch and bag length
  each round up to a power of two — so the number of distinct compiled
  programs is ``O(log(max_batch) · log(max_bag))``.  Padded bag slots
  carry ``mask = 0`` (``bag_pool`` conventions: they contribute exactly
  nothing) and padded batch rows are sliced off before scores land;
* **pipelines** waves: up to ``max_inflight`` dispatched programs ride
  JAX's async dispatch before the engine blocks on the oldest, so host
  wave-formation overlaps device execution (continuous mode only —
  legacy mode reaps synchronously);
* runs the **quantized forward** (int8/bf16 tables via ``serve.quantize``;
  the fused serve kernel when ``cfg.use_kernel``) split into an embed
  stage and a dense stage — both cache paths and the cache-off path feed
  the *same* jitted dense executable, which is what makes cache-on/off
  scores bit-comparable;
* serves hot rows from the **hot-row cache** when given: a
  ``DeviceHotRowCache`` keeps combined dequantized rows resident in
  device slabs — the hit path is one packed ``np.unique`` on the host,
  one slot-array build, and a single jitted gather→pool→project program;
  only *miss* rows are ever computed from the tables.  A host
  ``HotRowCache`` still works (rows pooled on host, compat path);
* tracks per-wave dispatch→ready wall time → **p50/p99 latency and QPS**
  via ``metrics()``.

Deterministic given (params, request stream): no sampling, logical-clock
cache, fixed bucket grid, sorted unique keys.
"""

from __future__ import annotations

import dataclasses
import time
from collections import Counter, deque
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import CompositionalEmbedding, HashEmbedding
from ..core.compositional import is_quantized_table, masked_bag_sum
from ..models.dcn import DCNConfig, dcn_forward_from_features
from ..models.dlrm import (DLRMConfig, dlrm_forward_from_features,
                           embed_features, tables_for)
from .cache import CacheStats, DeviceHotRowCache, HotRowCache

__all__ = ["RecRequest", "RecsysEngine", "BATCHING_MODES"]

BATCHING_MODES = ("continuous", "waves")

_FEATURE_SHIFT = 44  # packed key: (feature << 44) | canonical row id
# ceiling on the device slot map (int32 per cacheable row, 64 MiB):
# configs whose total canonical id space exceeds it skip the in-graph
# probe and use the exact host-side lookup instead
_SLOT_MAP_ROWS_MAX = 1 << 24


@dataclasses.dataclass
class RecRequest:
    uid: int
    dense: np.ndarray              # (dense_dim,)
    bags: list[list[int]]          # one multi-hot id bag per categorical
    score: Optional[float] = None
    done: bool = False
    t_submit: Optional[float] = None   # monotonic enqueue time (obs-on only)


# stage names: the five partition stages tile the measured wave-latency
# interval [dispatch t0, reap t1] with contiguous boundary timestamps, so
# their sum equals the recorded latency by construction (the serve_bench
# obs lane asserts it within 10%); queue_wait and pad happen before t0
# and ride along as extra, non-partition stages
STAGE_PARTITION = ("probe", "dense", "inflight", "miss_gather", "flush")
STAGES = ("queue_wait", "pad") + STAGE_PARTITION
# the tracer's host spans of one wave, in order (``cat`` "host": the
# engine's thread doing this wave's work); ``probe`` is split into the
# host-to-device transfer of the in-graph embed's ids and masks
# (``serve.h2d``, empty on the cache paths) and the rest of the embed
# dispatch (``serve.embed``), and the wave's own forming and score
# writing are spans of their own.  ``wave``, ``queue_wait`` and
# ``inflight`` are ``cat`` "interval" events: they overlap other work.
HOST_SPANS = ("serve.form_wave", "serve.pad", "serve.h2d", "serve.embed",
              "serve.dense", "serve.miss_gather", "serve.flush",
              "serve.scores")


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _dense_stage_for(cfg):
    if isinstance(cfg, DLRMConfig):
        return dlrm_forward_from_features
    if isinstance(cfg, DCNConfig):
        return dcn_forward_from_features
    raise TypeError(f"no recsys serving path for config {type(cfg).__name__}")


def _row_dtype(tp):
    """Dtype of the combined row ``module.apply`` yields for this table's
    params: f32 once any side is row-quantized (dequant widens), else the
    stored table dtype — the slab forward casts its f32 pooled bag back to
    this, mirroring ``bag_pool``."""
    sub = tp.get("table", tp.get("table_0"))
    return jnp.float32 if is_quantized_table(sub) else sub.dtype


class RecsysEngine:
    def __init__(self, cfg, params, *, max_batch: int = 32,
                 cache: Optional[HotRowCache] = None, mesh=None,
                 batching: str = "continuous", max_inflight: int = 2,
                 lookahead: Optional[int] = None,
                 mesh_devices: Optional[int] = None, placement=None,
                 plan=None, obs=None):
        if batching not in BATCHING_MODES:
            raise ValueError(f"batching={batching!r} not in {BATCHING_MODES}")
        self.cfg = cfg
        self.modules = tables_for(cfg)
        if cfg.embedding.kind == "feature":
            raise NotImplementedError(
                "feature-generation mode has no serving path (F varies)")
        self.cache = cache
        self.max_batch = max_batch
        self.batching = batching
        self.max_inflight = max_inflight
        self.lookahead = lookahead or 4 * max_batch
        self._n_shards = int(mesh_devices or 1)
        if self._n_shards > 1:
            if getattr(cfg, "use_kernel", False):
                raise NotImplementedError(
                    "sharded serving uses the jnp embed path, not the fused "
                    "kernel — build the config with use_kernel=False")
            if cache is not None and not isinstance(cache,
                                                    DeviceHotRowCache):
                raise NotImplementedError(
                    "sharded serving supports DeviceHotRowCache only (host "
                    "cache rows are not locally resident on a mesh)")
            if max_batch % self._n_shards or max_batch < self._n_shards:
                raise ValueError(
                    f"max_batch={max_batch} must be a positive multiple of "
                    f"mesh_devices={self._n_shards}")
            params = self._init_sharded(params, placement, plan)
        elif mesh is not None:
            # inference placement: same rules minus FSDP (read-only weights)
            from ..dist.sharding import INFERENCE_OVERRIDES, tree_shardings
            params = jax.device_put(
                params, tree_shardings(params, mesh, INFERENCE_OVERRIDES))
        self._install_model(cfg, params)
        self._sharded_embed = self._sharded_dense = self._sharded_fast = None
        if self._n_shards > 1:
            self._smap_mirror = self._slab_mirror = None
            self._mirror_version = None
            self._build_sharded(self._dense_stage, self._space_arr,
                                self._off_arr, self._w_index,
                                self._feat_width, self._row_dtypes)
        self._queue: deque[RecRequest] = deque()
        self._inflight: deque[tuple] = deque()
        self._next_uid = 0
        self.completed: dict[int, RecRequest] = {}
        self.wave_latencies_s: list[float] = []
        self.wave_sizes: list[int] = []
        # waves per embed path ("fast" slot-map probe, "exact" device-cache
        # lookup, "host_cache", "in_graph", "sharded*"); "*_miss" counts
        # speculative probes that missed and were recomputed exactly
        self.wave_paths: Counter = Counter()
        self.buckets_seen: set[tuple[int, int]] = set()
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None

        # observability: everything below is skipped when obs is None
        # (the off-by-default contract — obs-off waves take zero extra
        # clock reads and zero registry work, which is how the obs-on
        # lane's 2% QPS budget stays honest as a comparison)
        self._obs = obs
        if obs is not None:
            obs.attach_collisions(cfg.table_sizes)
            # label handles bound once: the hot path never hashes a dict
            hs = obs.histogram("serve_stage_seconds",
                               "per-wave stage durations (see STAGES)")
            self._h_stage = {s: hs.labels(stage=s) for s in STAGES}
            self._h_wave = obs.histogram(
                "serve_wave_latency_seconds",
                "dispatch->ready wall time per wave").labels()
            self._c_req = obs.counter(
                "serve_requests_total", "requests scored").labels()
            self._c_waves = obs.counter(
                "serve_waves_total", "waves dispatched").labels()
            self._c_wire = obs.counter(
                "serve_wire_bytes_total",
                "serve-exchange bytes moved between devices").labels(
                    collective="serve_exchange") \
                if self._n_shards > 1 else None
            self._wire_by_bucket: dict[tuple[int, int], int] = {}

    # ------------------------------------------------------------- model

    def _install_model(self, cfg, params) -> None:
        """Bind (cfg, params) and (re)build every program derived from
        them: embed/dense jits, the slab forward, the flat canonical-id
        layout, and the in-graph slot-map probe.  Called once from
        ``__init__`` and again by ``swap_plan`` — everything that depends
        on the plan's table structures lives here so a swap replaces it
        atomically (between waves; in-flight waves closed over the old
        programs and drain unaffected)."""
        self.cfg = cfg
        self.modules = tables_for(cfg)
        self.params = params
        dense_stage = _dense_stage_for(cfg)
        self._dense_stage = dense_stage

        def embed_fwd(params, idx, mask):
            feats = embed_features(params["tables"], idx, cfg, mask=mask,
                                   proj=params.get("proj"))
            return jnp.stack(feats, axis=1)

        # embed and dense stages jit separately: every path (cache off,
        # host cache, device cache) funnels its (B, F, D) features through
        # the *same* dense executable, so cache choice cannot perturb the
        # dense math
        self._embed_fwd = jax.jit(embed_fwd)
        self._dense_fwd = jax.jit(
            lambda params, dense, feats: dense_stage(params, dense, feats, cfg))

        # device-slab forward: one program per (slot-shape, slab-shape)
        # bucket — gather each feature's rows from its width's slab,
        # mask-pool in f32 (bag_pool convention), project mixed-dim
        # features into the interaction width
        widths = tuple(sorted({mod.out_dim for mod in self.modules}))
        w_index = {d: wi for wi, d in enumerate(widths)}
        feat_width = tuple(mod.out_dim for mod in self.modules)
        row_dtypes = tuple(_row_dtype(tp) for tp in params["tables"]) \
            if isinstance(params, dict) else ()
        self._widths = widths
        self._w_index = w_index
        self._feat_width = feat_width
        self._row_dtypes = row_dtypes

        def slab_fwd(proj, slabs, slots, mask):
            feats = []
            for i in range(len(feat_width)):
                rows = jnp.take(slabs[w_index[feat_width[i]]],
                                slots[:, i, :], axis=0)      # (B, L, d_i)
                pooled = masked_bag_sum(rows, mask[:, i, :]
                                        ).astype(row_dtypes[i])
                w = proj.get(str(i))
                feats.append(pooled if w is None else pooled @ w)
            return jnp.stack(feats, axis=1)

        self._slab_fwd = jax.jit(slab_fwd)

        # flat canonical-id layout for the device slot map: feature i's
        # canonical rows occupy [offset_i, offset_i + space_i), so one
        # int32 device array maps every cacheable row to its slab slot
        # (-1 = not resident) and the hit path probes it in-graph
        spaces = [mod.m if isinstance(mod, HashEmbedding) else size
                  for mod, size in zip(self.modules, cfg.table_sizes)]
        self._flat_offsets = np.concatenate(
            [[0], np.cumsum(spaces)[:-1]]).astype(np.int64)
        self._flat_total = int(sum(spaces))
        self._slot_map = None
        self._map_version = None

        # canonicalization is part of the probe program: hash features
        # fold mod m, QR/full ids are already < their space so the same
        # modulus is a no-op for them (everything stays int32)
        space_arr = jnp.asarray(spaces, jnp.int32)
        off_arr = jnp.asarray(self._flat_offsets, jnp.int32)
        self._space_arr = space_arr
        self._off_arr = off_arr

        def fast_fwd(smap, idx, mask, proj, slabs):
            flat = idx % space_arr[None, :, None] + off_arr[None, :, None]
            slots = jnp.take(smap, flat, axis=0)
            nmiss = jnp.sum((slots < 0) & (mask > 0))
            return slab_fwd(proj, slabs, slots, mask), nmiss

        # probe + gather + pool + project in ONE program: the fast path
        # costs the same number of dispatches as the in-graph embed
        self._fast_fwd = jax.jit(fast_fwd)

    def swap_plan(self, cfg, params, *, warm: bool = True) -> dict:
        """Hot-swap to a new plan's (cfg, params) without downtime.

        The zero-downtime contract, in dispatch order:

        1. **drain** — in-flight waves hold references to the old params,
           programs, and slabs, so they settle on the old plan (their
           scores are exactly what the old plan would have served);
        2. **invalidate** — every cached row is a *combined* row of the
           old structure, so the whole residency is dropped as
           invalidations (never evictions — capacity was not the cause;
           the cache property tests pin this), device slabs are released
           (widths may change), and ``residency_version`` moves so any
           slot-map consumer rebuilds;
        3. **install** — ``_install_model`` rebinds cfg/params and
           rebuilds every derived program and the flat id layout;
        4. **pre-warm** (``warm=True``) — every (batch, bag) bucket this
           engine has served is compiled against the new plan *now*,
           off the wave path, so post-swap p99 pays no XLA compiles.
           Warm traffic touches the cache (admitting each feature's row
           0) but never the obs telemetry — synthetic ids must not feed
           the drift detector.

        Single-host only (a sharded swap would need placement re-solve +
        resharding — see ROADMAP); the queue, uid space, metrics history,
        and completed map all survive the swap untouched.
        """
        if self._n_shards > 1:
            raise NotImplementedError(
                "swap_plan is single-host only: a sharded swap must also "
                "re-solve placement and reshard the tables")
        if cfg.embedding.kind == "feature":
            raise NotImplementedError(
                "feature-generation mode has no serving path (F varies)")
        if tuple(cfg.table_sizes) != tuple(self.cfg.table_sizes):
            raise ValueError("swap_plan keeps the feature set: table_sizes "
                             "must match the running config")
        while self._inflight:            # 1. drain on the old plan
            self._reap()
        dropped = 0
        if self.cache is not None:       # 2. stale residency out
            dropped = self.cache.invalidate_all()
        self._install_model(cfg, params)  # 3. new programs in
        if warm:                         # 4. compile before traffic lands
            self._warm_buckets()
        return {"invalidated_rows": dropped,
                "buckets_warmed": sorted(self.buckets_seen) if warm else [],
                "residency_version": getattr(self.cache,
                                             "residency_version", None)}

    def _warm_buckets(self) -> None:
        """Run one dummy wave per previously-seen (batch, bag) bucket
        through the same path selection as ``_dispatch`` — compiling the
        new plan's fast-probe, miss-gather, slab, and dense programs for
        every shape steady-state traffic will use.  Runs outside the
        wave/metrics/obs bookkeeping: latency histograms and collision
        telemetry never see these synthetic waves."""
        f = len(self.modules)
        dense_dim = getattr(self.cfg, "dense_dim", 13)
        for bb, lb in sorted(self.buckets_seen):
            dense = np.zeros((bb, dense_dim), np.float32)
            idx = np.zeros((bb, f, lb), np.int32)
            mask = np.zeros((bb, f, lb), np.float32)
            mask[:, :, 0] = 1.0  # one live slot: exercises the miss path
            if isinstance(self.cache, DeviceHotRowCache) \
                    and not self.cache.record_events:
                fast = self._embed_device_fast(idx, mask)
                feats = None
                if fast is not None:
                    feats, nmiss = fast
                    if int(nmiss):
                        feats = self._embed_device(idx, mask)
                if feats is None:
                    feats = self._embed_device(idx, mask)
            elif self.cache is not None:
                feats = jnp.asarray(self._embed_cached(idx, mask))
            else:
                feats = self._embed_fwd(self.params, jnp.asarray(idx),
                                        jnp.asarray(mask))
            jax.block_until_ready(
                self._dense_fwd(self.params, jnp.asarray(dense), feats))

    # ------------------------------------------------------------- sharding

    def _init_sharded(self, params, placement, plan):
        """Place the tables across a 1-D ``("data",)`` serve mesh per the
        plan-aware placement (``dist.serve_placement``): sub-tables below
        the replication threshold live on every device, big ones are
        row-sharded by quotient partition.  Returns the placed params."""
        from ..dist.serve_placement import place_params, plan_placement
        n = self._n_shards
        if jax.device_count() < n:
            raise ValueError(
                f"mesh_devices={n} but only {jax.device_count()} devices "
                "visible (CI emulates via --xla_force_host_platform_"
                "device_count)")
        from ..launch.mesh import make_mesh
        self._serve_mesh = make_mesh((n,), ("data",))
        if placement is None:
            placement = plan_placement(params, n, plan=plan)
        if placement.n_devices != n:
            raise ValueError(f"placement built for {placement.n_devices} "
                             f"devices, engine asked for {n}")
        self.placement = placement
        placed, self._param_specs = place_params(params, placement,
                                                 self._serve_mesh)
        # only fully-replicated features are cacheable: a row-sharded
        # feature's rows are not locally resident on every device, so the
        # device hot-row cache never admits them
        self._repl_live = placement.replicated_features(len(self.modules))
        return placed

    def _build_sharded(self, dense_stage, space_arr, off_arr, w_index,
                       feat_width, row_dtypes):
        """Sharded analogues of the single-host programs, same program
        boundaries (embed | dense | fast-probe) so each per-device
        computation is the *same XLA program* as its single-host
        counterpart at the per-device batch — that is what makes
        sharded-vs-single-host logits bit-identical (the serve_dist bench
        and tests assert it).  Row-sharded sub-tables fetch rows through
        the two-phase all-to-all exchange (``dist.serve_placement.
        exchange_rows``); everything else is local."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ..core.compositional import bag_pool, table_rows
        from ..dist.serve_placement import exchange_rows
        from ..models.dlrm import _project, embed_features
        cfg, n = self.cfg, self._n_shards
        rpd = {(e.feature, e.table_key): self.placement.rows_per_device(e)
               for e in self.placement.sharded}
        repl = tuple(bool(x) for x in self._repl_live)

        def gather_for(i):
            if repl[i]:
                return None  # fully local feature: plain bag_pool gather

            def g(leaf, ids, key):
                r = rpd.get((i, key))
                if r is None:  # replicated sub-table of a sharded feature
                    return table_rows(leaf, ids)
                return exchange_rows(leaf, ids, n, r, axis="data")
            return g

        gathers = [gather_for(i) for i in range(len(self.modules))]

        def embed_sh(params, idx, mask):
            feats = embed_features(params["tables"], idx, cfg, mask=mask,
                                   proj=params.get("proj"), gathers=gathers)
            return jnp.stack(feats, axis=1)

        def dense_sh(params, dense, feats):
            return dense_stage(params, dense, feats, cfg)

        def fast_sh(params, idx, mask, smap, slabs):
            # replicated features ride the slot-map probe exactly as the
            # single-host fast path; sharded features always go to their
            # tables (they are never cached); the miss count only sees
            # cacheable slots and is psum'd so every device agrees
            flat = idx % space_arr[None, :, None] + off_arr[None, :, None]
            slots = jnp.take(smap, flat, axis=0)
            proj = params.get("proj")
            feats, nmiss = [], jnp.int32(0)
            for i in range(len(self.modules)):
                if repl[i]:
                    rows = jnp.take(slabs[w_index[feat_width[i]]],
                                    slots[:, i, :], axis=0)
                    pooled = masked_bag_sum(rows, mask[:, i, :]
                                            ).astype(row_dtypes[i])
                    feats.append(_project(pooled, proj, i))
                    nmiss = nmiss + jnp.sum((slots[:, i, :] < 0)
                                            & (mask[:, i, :] > 0))
                else:
                    pooled = bag_pool(self.modules[i], params["tables"][i],
                                      idx[:, i, :], mask[:, i, :],
                                      gather=gathers[i])
                    feats.append(_project(pooled, proj, i))
            return jnp.stack(feats, axis=1), jax.lax.psum(nmiss, "data")

        mesh, specs = self._serve_mesh, self._param_specs
        self._sharded_embed = jax.jit(shard_map(
            embed_sh, mesh=mesh,
            in_specs=(specs, P("data"), P("data")), out_specs=P("data")))
        self._sharded_dense = jax.jit(shard_map(
            dense_sh, mesh=mesh,
            in_specs=(specs, P("data"), P("data")), out_specs=P("data")))
        self._sharded_fast = jax.jit(shard_map(
            fast_sh, mesh=mesh,
            in_specs=(specs, P("data"), P("data"), P(), P()),
            out_specs=(P("data"), P())))

    def _sharded_cache_state(self):
        """Slot map + slabs mirrored to every mesh device (replicated
        NamedSharding), refreshed only when cache residency changes.  The
        mirror is a copy: admission's donated scatter consumes the
        cache's own slab buffer, never the mirror the in-flight waves
        read."""
        ver = self.cache.residency_version
        if self._mirror_version != ver:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            rep = NamedSharding(self._serve_mesh, P())
            self._smap_mirror = jax.device_put(self._sync_slot_map(), rep)
            self._slab_mirror = tuple(
                jax.device_put(self.cache.slab(d), rep)
                for d in self._widths)
            self._mirror_version = ver
        return self._smap_mirror, self._slab_mirror

    def _admit_cacheable(self, idx: np.ndarray, mask: np.ndarray) -> None:
        """Sharded-mode admission half of ``_embed_device``: look up and
        admit this wave's *cacheable* (replicated-feature) rows with full
        per-key accounting, computing only the miss rows.  Features are
        not produced — the caller recomputes the wave through the pure
        sharded programs."""
        cache = self.cache
        f = idx.shape[1]
        live = (mask > 0) & np.asarray(self._repl_live)[None, :, None]
        canon = self._canonical(idx)
        packed = canon + (np.arange(f, dtype=np.int64)[None, :, None]
                          << _FEATURE_SHIFT)
        keys_live = packed[live]
        if not keys_live.size:
            return
        uniq, counts = np.unique(keys_live, return_counts=True)
        key_list = uniq.tolist()
        _, miss_u = cache.lookup_many(key_list, counts)
        if miss_u.any():
            rows = self._compute_miss_rows(uniq[miss_u])
            cache.put_many(uniq[miss_u].tolist(), rows, pinned=key_list)

    def _dispatch_sharded(self, dense, idx, mask):
        """Dispatch one wave through the sharded programs; returns
        ``(logits, check, ta, path)`` with the same speculative-probe
        contract as the single-host device-cache path (``ta`` is the
        probe/dense stage boundary timestamp, None when obs is off; ``path``
        names the embed path for ``wave_paths``)."""
        check = None
        if (isinstance(self.cache, DeviceHotRowCache)
                and not self.cache.record_events
                and self._flat_total <= _SLOT_MAP_ROWS_MAX
                and bool(np.asarray(self._repl_live).any())):
            smap, slabs = self._sharded_cache_state()
            feats, nmiss = self._sharded_fast(
                self.params, jnp.asarray(np.asarray(idx, np.int32)),
                jnp.asarray(mask), smap, slabs)
            check = (dense, idx, mask, nmiss)
            path = "sharded_fast"
        else:
            if self.cache is not None:
                self._admit_cacheable(idx, mask)
            feats = self._sharded_embed(self.params, jnp.asarray(idx),
                                        jnp.asarray(mask))
            path = "sharded"
        ta = time.monotonic() if self._obs is not None else None
        logits = self._sharded_dense(self.params, jnp.asarray(dense), feats)
        return logits, check, ta, path

    # ------------------------------------------------------------- intake

    def submit(self, dense, bags: Sequence[Sequence[int]]) -> int:
        """Queue one request.  Bags may be empty (legal in Criteo-style
        traffic: a user with no history for that feature) — an empty bag
        pools to the exact zero vector (its mask row is all zero, and the
        ``bag_pool`` / cache paths both honor that)."""
        if len(bags) != len(self.modules):
            raise ValueError(f"expected {len(self.modules)} feature bags, "
                             f"got {len(bags)}")
        uid = self._next_uid
        self._next_uid += 1
        self._queue.append(RecRequest(
            uid, np.asarray(dense, np.float32), [list(b) for b in bags],
            t_submit=(time.monotonic() if self._obs is not None else None)))
        return uid

    # ------------------------------------------------------------- batching

    @staticmethod
    def _bucket(r: RecRequest) -> int:
        return _next_pow2(max((len(b) for b in r.bags), default=1) or 1)

    def _form_wave(self) -> list[RecRequest]:
        """Next wave off the queue.

        Legacy mode: strict FIFO slice of up to ``max_batch``.  Continuous
        mode: the head request anchors the bag-length bucket; up to
        ``max_batch`` same-bucket requests within the first ``lookahead``
        queued requests join it, everything else keeps its place — the
        head always ships, so no request starves behind a hot bucket.
        """
        q = self._queue
        if not q:
            return []
        if self.batching == "waves":
            return [q.popleft() for _ in range(min(self.max_batch, len(q)))]
        anchor = self._bucket(q[0])
        wave: list[RecRequest] = []
        skipped: list[RecRequest] = []
        scanned = 0
        while q and len(wave) < self.max_batch and scanned < self.lookahead:
            r = q.popleft()
            scanned += 1
            if self._bucket(r) == anchor:
                wave.append(r)
            else:
                skipped.append(r)
        for r in reversed(skipped):
            q.appendleft(r)
        return wave

    def _pad_wave(self, wave: list[RecRequest]):
        """(dense (Bb, 13), idx (Bb, F, Lb) int32, mask (Bb, F, Lb) f32).

        ``Lb`` is at least 1 even for an all-empty wave (every bag empty):
        the padded slots carry mask 0, so they pool to zero vectors."""
        f = len(self.modules)
        lb = _next_pow2(max((len(b) for r in wave for b in r.bags),
                            default=1) or 1)
        if self._n_shards > 1:
            # bucket the *per-device* batch: the shard_map program each
            # device runs has batch Bb/n, and parity with a single-host
            # engine holds when that per-device batch equals its bucket
            per = -(-len(wave) // self._n_shards)
            bb = min(_next_pow2(per),
                     self.max_batch // self._n_shards) * self._n_shards
        else:
            bb = min(_next_pow2(len(wave)), self.max_batch)
        dense = np.zeros((bb, wave[0].dense.shape[0]), np.float32)
        idx = np.zeros((bb, f, lb), np.int32)
        mask = np.zeros((bb, f, lb), np.float32)
        for b, r in enumerate(wave):
            dense[b] = r.dense
            for i, bag in enumerate(r.bags):
                idx[b, i, :len(bag)] = bag
                mask[b, i, :len(bag)] = 1.0
        self.buckets_seen.add((bb, lb))
        return dense, idx, mask

    # ------------------------------------------------------------- cache path

    def _row_key(self, feature: int, gid: int):
        """(table, quotient, remainder) cache key for one raw id,
        canonicalized through the module's own bucketing so ids that share
        an embedding row share a cache entry (hash tables fold mod m)."""
        mod = self.modules[feature]
        if isinstance(mod, CompositionalEmbedding) and len(mod.partitions) == 2:
            m = mod.partitions[0].num_buckets
            return (feature, gid // m, gid % m)
        if isinstance(mod, HashEmbedding):
            return (feature, 0, gid % mod.m)
        return (feature, 0, gid)

    def _canonical(self, idx: np.ndarray) -> np.ndarray:
        """Fold raw ids (Bb, F, Lb) to canonical row ids per feature:
        hash tables share rows mod m; QR/full ids are already 1:1 with
        their (quotient, remainder) row, so the id itself canonicalizes."""
        canon = np.empty(idx.shape, np.int64)
        for i, mod in enumerate(self.modules):
            col = idx[:, i, :].astype(np.int64)
            canon[:, i, :] = col % mod.m if isinstance(mod, HashEmbedding) \
                else col
        return canon

    def _compute_miss_rows(self, miss_keys: np.ndarray) -> list:
        """Combined dequantized f32 rows for packed miss keys, one padded
        gather per feature (``module.apply`` is elementwise per row, so
        these rows are bit-identical to what the in-graph embed computes)."""
        feats_of = (miss_keys >> _FEATURE_SHIFT).astype(np.int64)
        gids = (miss_keys & ((1 << _FEATURE_SHIFT) - 1)).astype(np.int64)
        rows_out: list = [None] * len(miss_keys)
        for i in np.unique(feats_of):
            sel = np.flatnonzero(feats_of == i)
            ids = gids[sel]
            # pad the fill-gather to a floored power of two: the number of
            # distinct compiled gather shapes stays O(log) instead of one
            # per count, and the floor keeps small miss waves from
            # fragmenting into many tiny shape buckets
            n_pad = max(32, _next_pow2(len(ids)))
            padded = np.concatenate(
                [ids, np.repeat(ids[-1:], n_pad - len(ids))])
            rows = self.modules[int(i)].apply(
                self.params["tables"][int(i)], jnp.asarray(padded, jnp.int32))
            rows = jnp.asarray(rows, jnp.float32)
            for j, pos in enumerate(sel):
                rows_out[int(pos)] = rows[j]
        return rows_out

    def _sync_slot_map(self):
        """Device slot map (flat canonical id -> slab slot, -1 = miss),
        rebuilt from the cache's residency only when it changed — at a
        steady hit rate this is a no-op and the hit path never touches a
        Python dict."""
        ver = self.cache.residency_version
        if self._slot_map is None or ver != self._map_version:
            smap = np.full(self._flat_total, -1, np.int32)
            keys, slots = self.cache.slot_items()
            if len(keys):
                feats = keys >> _FEATURE_SHIFT
                canon = keys & ((1 << _FEATURE_SHIFT) - 1)
                smap[self._flat_offsets[feats] + canon] = slots
            self._slot_map = jnp.asarray(smap)
            self._map_version = ver
        return self._slot_map

    def _embed_device_fast(self, idx: np.ndarray, mask: np.ndarray):
        """Speculative wave via the in-graph slot-map probe: fold ids,
        probe the map, gather/pool/project from the slabs — all
        dispatched asynchronously with **zero** per-key host work and no
        host<->device sync.  Returns ``(feats, nmiss)`` where ``nmiss``
        is a device scalar the caller checks *at reap time* (it is ready
        by then): nonzero means some row was not resident, the
        speculative features are garbage, and the wave is recomputed
        through the exact path.  Returns ``None`` when the config's id
        space is too big to map.

        Dispatch order makes speculation safe: a later admission's
        donated scatter executes after this wave's gathers, so the slabs
        this program reads are exactly the slabs that were resident when
        it was dispatched.

        The fast path batches accounting: per-wave hit totals land in
        ``stats`` but per-key LFU/LRU freshness is only refreshed by the
        exact path (miss waves and ``record_events`` runs), so eviction
        order under pressure leans on admission-time frequencies.  Runs
        that need exact per-key accounting (the replay/property tests,
        anything setting ``record_events=True``) always take the exact
        path."""
        if self._flat_total > _SLOT_MAP_ROWS_MAX:
            return None
        smap = self._sync_slot_map()
        proj = self.params.get("proj") if isinstance(self.params, dict) \
            else None
        slabs = tuple(self.cache.slab(d) for d in self._widths)
        return self._fast_fwd(smap, jnp.asarray(np.asarray(idx, np.int32)),
                              jnp.asarray(mask), proj or {}, slabs)

    def _embed_device(self, idx: np.ndarray, mask: np.ndarray):
        """Wave features via the device-resident cache: one packed
        ``np.unique`` over the wave's live (feature, row) keys, slot
        lookups host-side, miss rows computed once and admitted through a
        batched donated scatter, then a single jitted slab
        gather→pool→project.  Rows never round-trip to the host.

        This is the *exact* path: it performs full per-key accounting
        (stats, LFU/LRU freshness, event log) with host semantics
        identical to ``HotRowCache``.  ``_dispatch`` first tries the
        speculative ``_embed_device_fast`` probe and only lands here for
        miss waves, oversized id spaces, or ``record_events`` runs.

        The whole wave's keys are pinned during admission so an in-wave
        eviction can never reassign a slot the gather is about to read;
        if admission is refused anyway (cache smaller than the wave's
        working set), the wave falls back to the in-graph embed — same
        bits, no cache."""
        cache = self.cache
        bb, f, lb = idx.shape
        canon = self._canonical(idx)
        packed = canon + (np.arange(f, dtype=np.int64)[None, :, None]
                          << _FEATURE_SHIFT)
        live = mask > 0
        keys_live = packed[live]
        if keys_live.size:
            uniq, inv, counts = np.unique(
                keys_live, return_inverse=True, return_counts=True)
        else:
            uniq = np.empty(0, np.int64)
            inv = np.empty(0, np.int64)
            counts = np.empty(0, np.int64)
        key_list = uniq.tolist()
        slots_u, miss_u = cache.lookup_many(key_list, counts)
        if miss_u.any():
            miss_keys = uniq[miss_u]
            rows = self._compute_miss_rows(miss_keys)
            admitted = cache.put_many(miss_keys.tolist(), rows,
                                      pinned=key_list)
            if len(admitted) != len(miss_keys):
                # working set exceeds the pinnable capacity: serve this
                # wave in-graph (identical math; stats already counted)
                return self._embed_fwd(self.params, jnp.asarray(idx),
                                       jnp.asarray(mask))
            # hit slots survive admission (the whole wave is pinned, so
            # no hit row was evicted): only the misses need re-resolving
            slots_u[miss_u] = cache.slots_for(miss_keys.tolist())
        slots = np.zeros((bb, f, lb), np.int32)
        if key_list:
            slots[live] = slots_u[inv]
        slabs = tuple(cache.slab(d) for d in self._widths)
        proj = self.params.get("proj") if isinstance(self.params, dict) \
            else None
        return self._slab_fwd(proj or {}, slabs, jnp.asarray(slots),
                              jnp.asarray(mask))

    def _embed_cached(self, idx: np.ndarray, mask: np.ndarray) -> np.ndarray:
        """Pooled features (Bb, F, D) via the host hot-row cache.

        Cached unit: the *combined* (post-op, dequantized) f32 row per
        (table, quotient, remainder), at the table's **own width** —
        mixed-dimension plans cache narrow rows narrow, and the pooled
        bag is projected into the interaction width afterwards (pooling
        and projection are both linear, so pool-then-project matches the
        jitted in-graph path).  An empty bag has no live slots and stays
        the zero vector.  Misses are computed in one gather per feature
        over the unique missing ids and admitted.
        """
        bb, f, lb = idx.shape
        d = self.cfg.emb_dim
        proj = self.params.get("proj") if isinstance(self.params, dict) \
            else None
        feats = np.zeros((bb, f, d), np.float32)
        for i, mod in enumerate(self.modules):
            di = mod.out_dim
            pooled = np.zeros((bb, di), np.float32)
            live = np.argwhere(mask[:, i, :] > 0)
            gids = [int(idx[b, i, l]) for b, l in live]
            keys = [self._row_key(i, g) for g in gids]
            found, missing = self.cache.get_many(keys)
            if missing:
                miss_set = set(missing)
                miss_gids = sorted({g for g, k in zip(gids, keys)
                                    if k in miss_set})
                # pad the fill-gather to a floored power of two: the number
                # of distinct compiled gather shapes stays O(log max_batch)
                # instead of one per unique miss count
                padded = miss_gids + [miss_gids[-1]] * \
                    (max(32, _next_pow2(len(miss_gids))) - len(miss_gids))
                rows = np.asarray(mod.apply(
                    self.params["tables"][i],
                    jnp.asarray(padded, jnp.int32)), np.float32)
                for g, row in zip(miss_gids, rows):
                    found[self._row_key(i, g)] = row
                    self.cache.put(self._row_key(i, g), row)
            for (b, l), key in zip(live, keys):
                pooled[b] += mask[b, i, l] * found[key]
            w = None if proj is None else proj.get(str(i))
            feats[:, i, :] = pooled if w is None \
                else pooled @ np.asarray(w, np.float32)
        return feats

    # ------------------------------------------------------------- execution

    def _dispatch(self, wave: list[RecRequest],
                  tf: Optional[float] = None) -> None:
        """``tf``: when the tracer is on, the monotonic time the wave
        began to form (the start of its ``serve.form_wave`` span)."""
        obs = self._obs
        tq = time.monotonic() if obs is not None else None
        dense, idx, mask = self._pad_wave(wave)
        t0 = time.monotonic()
        if obs is not None and obs.collisions is not None:
            # raw served ids for the measured collision mass; idx/mask
            # are this wave's own buffers, so holding references is safe
            obs.collisions.record(idx, mask, live_rows=len(wave))
        check = None
        th = t0
        if self._n_shards > 1:
            logits, check, ta, path = self._dispatch_sharded(dense, idx,
                                                             mask)
        else:
            if isinstance(self.cache, DeviceHotRowCache):
                fast = None if self.cache.record_events \
                    else self._embed_device_fast(idx, mask)
                if fast is not None:
                    feats, nmiss = fast
                    check = (dense, idx, mask, nmiss)
                    path = "fast"
                else:
                    feats = self._embed_device(idx, mask)
                    path = "exact"
            elif self.cache is not None:
                feats = jnp.asarray(self._embed_cached(idx, mask))
                path = "host_cache"
            else:
                # moving the ids and masks is a span of its own when traced
                # (the cache paths move them inside the embed); the device
                # copies are let go once the embed holds them
                idx_d, mask_d = jnp.asarray(idx), jnp.asarray(mask)
                if tf is not None:
                    th = time.monotonic()
                feats = self._embed_fwd(self.params, idx_d, mask_d)
                del idx_d, mask_d
                path = "in_graph"
            ta = time.monotonic() if obs is not None else None
            logits = self._dense_fwd(self.params, jnp.asarray(dense), feats)
        self._t_first = t0 if self._t_first is None else self._t_first
        oi = None
        if obs is not None:
            tb = time.monotonic()
            waits = [tq - r.t_submit for r in wave if r.t_submit is not None]
            oi = {"tf": tf, "tq": tq, "t0": t0, "th": th, "ta": ta,
                  "tb": tb,
                  "queue_wait": max(waits) if waits else 0.0,
                  "n": len(wave), "bb": idx.shape[0], "lb": idx.shape[2]}
            if self._c_wire is not None:
                bucket = (idx.shape[0] // self._n_shards, idx.shape[2])
                wb = self._wire_by_bucket.get(bucket)
                if wb is None:
                    from ..dist.accounting import serve_wave_wire_bytes
                    wb = int(serve_wave_wire_bytes(
                        self.placement, bucket[0],
                        bucket[1])["total_bytes"])
                    self._wire_by_bucket[bucket] = wb
                self._c_wire.inc(wb)
        self._inflight.append((wave, logits, t0, check, oi, path))

    def _reap(self) -> list[RecRequest]:
        wave, logits, t0, check, oi, path = self._inflight.popleft()
        tc = time.monotonic() if oi is not None else None
        if check is not None:
            # settle the speculative probe: by reap time the async miss
            # count has materialized, so this blocks on nothing extra
            dense, idx, mask, nmiss = check
            if int(nmiss):
                path += "_miss"
            if int(nmiss) and self._n_shards > 1:
                # some cacheable row was not resident: admit it with exact
                # accounting, then recompute through the pure programs
                self._admit_cacheable(idx, mask)
                feats = self._sharded_embed(self.params, jnp.asarray(idx),
                                            jnp.asarray(mask))
                logits = self._sharded_dense(self.params,
                                             jnp.asarray(dense), feats)
            elif int(nmiss):
                feats = self._embed_device(idx, mask)   # exact: admit+count
                logits = self._dense_fwd(self.params, jnp.asarray(dense),
                                         feats)
            else:
                live = mask > 0
                if self._n_shards > 1:  # only cacheable slots were probed
                    live = live & np.asarray(self._repl_live)[None, :, None]
                self.cache.stats.hits += int(live.sum())
        td = time.monotonic() if oi is not None else None
        logits = np.asarray(jax.block_until_ready(logits), np.float32)
        t1 = time.monotonic()
        self._t_last = t1
        self.wave_latencies_s.append(t1 - t0)
        self.wave_sizes.append(len(wave))
        self.wave_paths[path] += 1
        for b, r in enumerate(wave):  # padded rows beyond len(wave) discarded
            r.score = float(logits[b])
            r.done = True
            self.completed[r.uid] = r
        if oi is not None:
            ts = time.monotonic() if oi["tf"] is not None else None
            self._record_wave(oi, tc, td, t1, ts)
        return wave

    def step(self) -> list[RecRequest]:
        """Form + dispatch one wave, reap what's due; returns finished
        requests.  Legacy mode reaps synchronously (wave in, scores out);
        continuous mode lets up to ``max_inflight`` waves ride JAX async
        dispatch and only blocks on the oldest beyond that (or drains when
        the queue is empty)."""
        obs = self._obs
        tf = time.monotonic() \
            if obs is not None and obs.tracer is not None else None
        wave = self._form_wave()
        if wave:
            self._dispatch(wave, tf)
        limit = 0 if self.batching == "waves" else self.max_inflight
        done: list[RecRequest] = []
        while self._inflight and (len(self._inflight) > limit
                                  or not self._queue):
            done.extend(self._reap())
        return done

    def run_until_drained(self) -> dict[int, RecRequest]:
        while self._queue or self._inflight:
            self.step()
        return self.completed

    # ------------------------------------------------------------- metrics

    def _record_wave(self, oi: dict, tc: float, td: float, t1: float,
                     ts: Optional[float]) -> None:
        """Fold one reaped wave's boundary timestamps into the registry
        (and tracer).  The five partition stages tile [t0, t1] exactly:
        probe (embed/cache-probe dispatch), dense (dense dispatch),
        inflight (async pipeline gap until reap), miss_gather (settling
        the speculative probe — recompute on miss, accounting on hit),
        flush (the block_until_ready sync).  With the tracer on (``ts``,
        the end of score writing, is then set) the wave's ``HOST_SPANS``
        tile [form start, ts] but for its time in flight."""
        obs = self._obs
        t0, ta, tb = oi["t0"], oi["ta"], oi["tb"]
        stages = (("queue_wait", oi["tq"] - oi["queue_wait"],
                   oi["queue_wait"]),
                  ("pad", oi["tq"], t0 - oi["tq"]),
                  ("probe", t0, ta - t0),
                  ("dense", ta, tb - ta),
                  ("inflight", tb, tc - tb),
                  ("miss_gather", tc, td - tc),
                  ("flush", td, t1 - td))
        for name, _, dur in stages:
            self._h_stage[name].observe(dur)
        self._h_wave.observe(t1 - t0)
        self._c_req.inc(oi["n"])
        self._c_waves.inc()
        tr = obs.tracer
        if tr is not None and ts is not None:
            tr.complete("wave", t0, t1 - t0, cat="interval",
                        requests=oi["n"], batch=oi["bb"], bag=oi["lb"])
            tr.complete("queue_wait", oi["tq"] - oi["queue_wait"],
                        oi["queue_wait"], cat="interval")
            tr.complete("inflight", tb, tc - tb, cat="interval")
            bounds = ((oi["tf"], oi["tq"]), (oi["tq"], t0), (t0, oi["th"]),
                      (oi["th"], ta), (ta, tb), (tc, td), (td, t1), (t1, ts))
            for name, (start, end) in zip(HOST_SPANS, bounds):
                tr.complete(name, start, end - start)

    def stage_summary(self) -> dict:
        """Per-stage latency summaries plus the partition check the obs
        lane asserts: the five partition stages must sum to the recorded
        wave latency (same clock reads, contiguous boundaries)."""
        if self._obs is None:
            raise RuntimeError("stage_summary() needs an Obs-enabled engine")
        out = {s: self._h_stage[s].summary() for s in STAGES}
        stage_sum = sum(out[s]["sum"] for s in STAGE_PARTITION)
        lat = self._h_wave.summary()
        out["partition"] = {
            "stage_sum_s": stage_sum,
            "latency_sum_s": lat["sum"],
            "ratio": (stage_sum / lat["sum"]) if lat["sum"] else 1.0,
        }
        return out

    def reset_metrics(self) -> None:
        """Drop timing history AND traffic counters (benches call this
        after bucket warm-up so p50/p99 — and cache hit rates — measure
        steady-state serving, not jit compilation or cold fills).  Cache
        residency (``bytes_cached``) survives: the rows are still
        resident, only the traffic counters restart.  Obs serve_* series
        reset too; bound label handles stay live."""
        self.wave_latencies_s = []
        self.wave_sizes = []
        self.wave_paths = Counter()
        self._t_first = self._t_last = None
        if self.cache is not None:
            self.cache.stats = CacheStats(
                bytes_cached=self.cache.stats.bytes_cached)
        if self._obs is not None:
            self._obs.registry.reset(prefix="serve_")

    def compile_count(self) -> dict:
        """Per-program jit compile counts — the pow2-bucket bound made
        introspectable.  Reads each wrapper's compile cache (no timing, no
        dispatch): the analyzer's jit-cache watcher and the regression
        test both gate on these numbers, so a padding bug that sneaks an
        unbucketed shape into the hot path shows up as an excess compile,
        not as a latency mystery.  ``swap_plan`` rebuilds the wrappers, so
        counts restart from zero at install (matching what the engine can
        recompile after a swap).  Returns ``{"per_program": {...},
        "total": n}``; wrappers whose cache the jax version cannot report
        are listed as ``None`` and excluded from the total."""
        wrappers = {"embed": self._embed_fwd, "dense": self._dense_fwd,
                    "slab": self._slab_fwd, "fast": self._fast_fwd,
                    "sharded_embed": self._sharded_embed,
                    "sharded_dense": self._sharded_dense,
                    "sharded_fast": self._sharded_fast}
        per: dict[str, Optional[int]] = {}
        total = 0
        for name, fn in wrappers.items():
            if fn is None:
                continue
            size = getattr(fn, "_cache_size", None)
            per[name] = int(size()) if callable(size) else None
            if per[name] is not None:
                total += per[name]
        return {"per_program": per, "total": total}

    def metrics(self) -> dict:
        lat = np.asarray(self.wave_latencies_s or [0.0])
        wall = ((self._t_last - self._t_first)
                if self._t_first is not None and self._t_last is not None
                else 0.0)
        out = {
            "requests": int(sum(self.wave_sizes)),
            "waves": len(self.wave_sizes),
            "batching": self.batching,
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "qps": (sum(self.wave_sizes) / wall) if wall > 0 else 0.0,
            "buckets": sorted(self.buckets_seen),
            "paths": dict(self.wave_paths),
        }
        if self.cache is not None:
            out["cache"] = self.cache.stats.as_dict()
            if self._obs is not None:
                # fold residency + traffic into gauges at scrape time
                # (never per wave: this walk is not hot-path work)
                g = self._obs.gauge("serve_cache_stat",
                                    "hot-row cache stats at last scrape")
                for k, v in out["cache"].items():
                    g.set(float(v), stat=k)
        return out
