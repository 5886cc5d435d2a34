"""Fault-tolerant training loop.

Properties engineered for 1000+-node runs and tested here at small scale:

* **restart determinism** — data is stateless-per-step and the PRNG is
  folded from the step counter, so kill-at-step-k + resume replays the
  exact stream; the restart test asserts bitwise-equal losses.
* **atomic async checkpoints** — see ``repro.ckpt``; the loop resumes from
  the newest *valid* checkpoint (corrupt/partial ones are skipped).
* **straggler watchdog** — per-step wall time is tracked; steps slower
  than ``watchdog_factor ×`` the running median are logged as straggler
  events (on a real cluster this feeds the reshard/evict policy; here it
  surfaces in metrics so tests can assert on it).
* **gradient compression** — bf16/int8 error-feedback reduction for the
  data-parallel axis, uniform or per-leaf via a ``CompressionPolicy``
  (shard_map path; see repro.dist.compress / repro.dist.policy).
* **reduce-scatter FSDP grad path** — ``make_fsdp_train_step`` reduce-
  scatters compressed gradients, applies the optimizer on each device's
  shard (opt state sharded: per-device optimizer memory ÷ N), and
  all-gathers the updated params.  Scatter dims come from the sharding
  rule engine (``sharding.scatter_dims``).
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ckpt import checkpoint as ckpt
from ..dist.compress import (_bf16_from_wire, _bf16_to_wire, _reduce_leaf,
                             _reduce_scatter_leaf, ef_psum_grads,
                             init_error_state, resolve_modes)
from ..optim.optimizers import (Optimizer, clip_by_global_norm, leaf_paths,
                                state_structs)

__all__ = ["TrainConfig", "init_state", "make_train_step", "make_dp_train_step",
           "make_fsdp_train_step", "init_dp_state", "init_fsdp_state",
           "fsdp_plan", "Trainer", "SimulatedFailure"]


class SimulatedFailure(RuntimeError):
    """Raised by the loop's fault-injection hook (tests)."""


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    num_steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    keep: int = 3
    clip_norm: Optional[float] = None
    watchdog_factor: float = 3.0


# one program for the whole tree (an eager copy compiles one per shape)
_copy_tree = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))


def init_state(params, optimizer: Optimizer):
    """The train state, its params copied once into buffers of their own:
    a ``Trainer`` consumes the state it is handed, and the caller's
    ``params`` stay readable."""
    return {"params": _copy_tree(params), "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


def make_train_step(loss_fn, optimizer: Optimizer, *, clip_norm=None,
                    accum: int = 1, accum_dtype=jnp.float32):
    """Standard pjit-able step: grads → (clip) → optimizer → new state.

    ``accum`` > 1 enables gradient accumulation: the global batch is split
    into ``accum`` microbatches processed by a ``lax.scan`` (activation
    memory ÷ accum — what lets the 34B+ archs fit 16 GB/chip at the
    assigned train_4k batch of 256 sequences).  Gradients accumulate in
    f32; loss/metrics are microbatch means, bitwise independent of accum
    for linear losses.

    The loss runs under ``jax.named_scope("forward")`` and the optimizer
    under ``"optimizer"``: HLO metadata only, so a profile can split the
    step's device time (backward ops carry ``transpose(jvp(forward))``).
    """

    def _forward(params, batch):
        with jax.named_scope("forward"):
            return loss_fn(params, batch)

    def _grads(params, batch):
        return jax.value_and_grad(_forward, has_aux=True)(params, batch)

    def step(state, batch):
        if accum == 1:
            (loss, metrics), grads = _grads(state["params"], batch)
        else:
            from ..dist.sharding import constrain_batch

            def split(x):
                mb = x.reshape((accum, x.shape[0] // accum) + x.shape[1:])
                return mb

            micro = jax.tree.map(split, batch)

            def mb_step(carry, mbatch):
                g_acc, loss_acc = carry
                mbatch = jax.tree.map(constrain_batch, mbatch)
                (loss, metrics), g = _grads(state["params"], mbatch)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(accum_dtype), g_acc, g)
                return (g_acc, loss_acc + loss), metrics

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype),
                              state["params"])
            (grads, loss_sum), metricss = jax.lax.scan(
                mb_step, (g0, jnp.float32(0.0)), micro)
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = loss_sum / accum
            metrics = jax.tree.map(lambda m: m.mean(), metricss)
        if clip_norm is not None:
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
            metrics = dict(metrics, grad_norm=gnorm)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(
                grads, state["opt"], state["params"], state["step"])
        new_state = {"params": new_params, "opt": new_opt, "step": state["step"] + 1}
        return new_state, dict(metrics, loss=loss)

    return step


def _resolve_compress(compress):
    """``"auto"`` / policy / mode string / per-leaf tree → ef_psum_grads mode."""
    from ..dist.policy import resolve_policy
    if isinstance(compress, str):
        return resolve_policy(compress)
    return compress


def make_dp_train_step(loss_fn, optimizer: Optimizer, mesh, *,
                       compress="bf16", clip_norm=None, axis: str = "data"):
    """Explicit data-parallel step via shard_map with compressed grad reduction.

    Params/opt-state replicated; batch sharded over ``axis``; gradients
    reduced with bf16/int8 error feedback (state carried in ``state['err']``).
    ``compress`` is a mode string, ``"auto"``, a ``CompressionPolicy``, or a
    per-leaf mode pytree.  The per-replica update math is identical, so
    replicas stay bitwise consistent without re-broadcast.
    """
    from jax import shard_map
    compress = _resolve_compress(compress)

    def _step(state, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"], batch)
        grads, new_err = ef_psum_grads(grads, state["err"], axis_name=axis,
                                       mode=compress)
        loss = jax.lax.pmean(loss, axis)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, axis), metrics)
        if clip_norm is not None:
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
            metrics = dict(metrics, grad_norm=gnorm)
        new_params, new_opt = optimizer.update(grads, state["opt"],
                                               state["params"], state["step"])
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1, "err": new_err}
        return new_state, dict(metrics, loss=loss)

    return shard_map(_step, mesh=mesh,
                     in_specs=(P(), P(axis)),
                     out_specs=(P(), P()),
                     check_vma=False)


def init_dp_state(params, optimizer: Optimizer, compress=None):
    """State for ``make_dp_train_step``.  Pass the same ``compress`` policy as
    the step so error-feedback state is allocated only for compressed leaves."""
    err = init_error_state(
        params, _resolve_compress(compress) if compress is not None else None)
    return {"params": params, "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32), "err": err}


# --------------------------------------------------------------- FSDP path


def _axis_size(mesh, axis: str) -> int:
    return dict(mesh.shape).get(axis, 1)


def fsdp_plan(params_like, optimizer: Optimizer, mesh, *, policy="auto",
              axis: str = "data"):
    """Per-leaf FSDP plan: ``[(path, shape, mode, scatter_dim | None)]``.

    The scatter dim is the first ``sharding.scatter_dims`` candidate along
    which every optimizer-state leaf of that param is sliceable (its size
    there equals the param's — e.g. row-wise Adagrad's ``(rows, 1)``
    accumulator admits dim 0 only, Adafactor's factored stats admit none,
    so those leaves safely fall back to the replicated all-reduce path).
    """
    from ..dist.sharding import scatter_dims
    paths = leaf_paths(params_like)
    leaves = jax.tree.leaves(params_like)
    modes = resolve_modes(params_like, _resolve_compress(policy))
    opt_structs = state_structs(optimizer, params_like)
    plan = []
    for path, leaf, mode, entry in zip(paths, leaves, modes, opt_structs):
        shape = tuple(leaf.shape)
        dim = None
        for d in scatter_dims(path, shape, mesh, axis):
            if all(len(s.shape) > d and s.shape[d] == shape[d]
                   for s in jax.tree.leaves(entry)):
                dim = d
                break
        plan.append((path, shape, mode, dim))
    return plan


def make_fsdp_train_step(loss_fn, optimizer: Optimizer, mesh, params_like, *,
                         policy="auto", clip_norm=None, axis: str = "data",
                         param_gather_dtype="float32"):
    """Reduce-scatter FSDP step: compressed gradients land as shards.

    Per leaf (scatter dim from ``fsdp_plan``): reduce-scatter the
    compressed gradient over ``axis``, apply the optimizer to this
    device's param shard against its **sharded optimizer state**
    (per-device optimizer memory ÷ N — for DLRM-scale models the
    optimizer accumulators rival the embedding tables themselves), then
    all-gather the updated shards back into replicated params for the
    next forward.  Leaves with no viable scatter dim take the replicated
    compressed all-reduce path; the two coexist in one step.

    ``params_like`` (arrays or ShapeDtypeStructs) fixes leaf paths/shapes
    at trace time.  Error-feedback residuals are genuinely per-device
    here: state ``err`` leaves are ``(n_devices, *leaf_shape)`` arrays
    sharded over ``axis`` (use ``init_fsdp_state``).  Supported
    optimizers are those whose ``update_leaf`` is element-wise or
    row-preserving along the scatter dim (SGD/Adagrad/Adam; row-wise
    Adagrad scatters rows); Adafactor leaves fall back to all-reduce
    automatically.

    ``param_gather_dtype="bfloat16"`` halves the param all-gather wire
    (the FSDP step's other big collective): updated shards ride as
    bitcast uint16 — the same trick as the compressed grad exchanges,
    since a plain bf16 all-gather gets silently retyped f32 on backends
    without native bf16 collectives — and each device then overwrites its
    own slice with its exact f32 shard, so the *master* shard never loses
    precision; only the replicated copies of **other** devices' shards
    are bf16-rounded (one bf16 ulp on the forward, ~2^-9 relative).
    """
    from jax import shard_map
    n = _axis_size(mesh, axis)
    gather_bf16 = jnp.dtype(param_gather_dtype) == jnp.bfloat16
    if not gather_bf16 and jnp.dtype(param_gather_dtype) != jnp.float32:
        raise ValueError(f"param_gather_dtype must be float32 or bfloat16, "
                         f"got {param_gather_dtype!r}")
    plan = fsdp_plan(params_like, optimizer, mesh, policy=policy, axis=axis)
    treedef = jax.tree.structure(params_like)
    opt_structs = state_structs(optimizer, params_like)

    def _opt_spec(entry, dim):
        if dim is None:
            return jax.tree.map(lambda s: P(), entry)
        return jax.tree.map(lambda s: P(*([None] * dim + [axis])), entry)

    state_specs = {
        "params": P(),
        "opt": [_opt_spec(entry, dim)
                for entry, (_, _, _, dim) in zip(opt_structs, plan)],
        "step": P(),
        "err": P(axis),
    }

    def _step(state, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"], batch)
        idx = jax.lax.axis_index(axis)
        flat_g = jax.tree.leaves(grads)
        flat_p = jax.tree.leaves(state["params"])
        flat_e = jax.tree.leaves(state["err"])

        red, new_err, p_local = [], [], []
        for g, p, e_blk, (_path, shape, mode, dim) in zip(flat_g, flat_p,
                                                         flat_e, plan):
            e = e_blk.reshape(e_blk.shape[1:])  # drop the device dim
            if dim is None:
                r, ne = _reduce_leaf(g, e, axis, mode)
                r = r.astype(jnp.float32)
                p_loc = p
            else:
                r, ne = _reduce_scatter_leaf(g, e, axis, mode, dim)
                shard = shape[dim] // n
                p_loc = jax.lax.dynamic_slice_in_dim(p, idx * shard, shard,
                                                     axis=dim)
            red.append(r)
            new_err.append(ne.reshape((1,) + ne.shape))
            p_local.append(p_loc)

        if clip_norm is not None:
            # shard-aware global norm: scattered leaves psum their shard
            # energy; replicated leaves are already identical everywhere.
            local = sum(jnp.sum(jnp.square(r))
                        for r, (_, _, _, d) in zip(red, plan) if d is not None)
            scat = jax.lax.psum(local, axis) if not isinstance(local, int) else 0.0
            rep = sum(jnp.sum(jnp.square(r))
                      for r, (_, _, _, d) in zip(red, plan) if d is None)
            gnorm = jnp.sqrt(scat + rep)
            scale = jnp.minimum(1.0, clip_norm / (gnorm + 1e-9))
            red = [r * scale for r in red]
            metrics = dict(metrics, grad_norm=gnorm)

        g_tree = jax.tree.unflatten(treedef, red)
        p_tree = jax.tree.unflatten(treedef, p_local)
        new_p_local, new_opt = optimizer.update(g_tree, state["opt"], p_tree,
                                                state["step"])
        new_params = []
        for np_loc, (_path, shape, _mode, dim) in zip(
                jax.tree.leaves(new_p_local), plan):
            if dim is None:
                new_params.append(np_loc)
            elif gather_bf16:
                wire = jax.lax.all_gather(
                    _bf16_to_wire(np_loc.astype(jnp.float32)), axis,
                    axis=dim, tiled=True)
                full = _bf16_from_wire(wire).astype(np_loc.dtype)
                # this device's master shard stays exact
                full = jax.lax.dynamic_update_slice_in_dim(
                    full, np_loc, idx * (shape[dim] // n), axis=dim)
                new_params.append(full)
            else:
                new_params.append(jax.lax.all_gather(np_loc, axis,
                                                     axis=dim, tiled=True))
        loss = jax.lax.pmean(loss, axis)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, axis), metrics)
        new_state = {"params": jax.tree.unflatten(treedef, new_params),
                     "opt": new_opt, "step": state["step"] + 1,
                     "err": jax.tree.unflatten(treedef, new_err)}
        return new_state, dict(metrics, loss=loss)

    return shard_map(_step, mesh=mesh,
                     in_specs=(state_specs, P(axis)),
                     out_specs=(state_specs, P()),
                     check_vma=False)


def init_fsdp_state(params, optimizer: Optimizer, mesh, *, policy="auto",
                    axis: str = "data"):
    """State for ``make_fsdp_train_step``: per-device error-feedback
    residuals (``(n, *shape)``, sharded over ``axis`` by the step's
    in_specs), residual placeholders for uncompressed leaves."""
    n = _axis_size(mesh, axis)
    modes = resolve_modes(params, _resolve_compress(policy))
    leaves, treedef = jax.tree.flatten(params)
    # placeholder for uncompressed leaves is (n,) — a per-device 0-d
    # residual, so the step's reshape(shape[1:]) broadcasts without
    # promoting rank-0 gradients to (1,)
    err = [jnp.zeros((n,) if m == "none" else (n,) + jnp.shape(g),
                     jnp.float32)
           for g, m in zip(leaves, modes)]
    return {"params": params, "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32),
            "err": jax.tree.unflatten(treedef, err)}


class Trainer:
    def __init__(self, train_step, cfg: TrainConfig, *,
                 batch_at: Callable[[int], Any], obs=None, step_wire=None):
        """``train_step`` is jitted with the state donated: the step writes
        the new params and optimizer state into the buffers of the state
        passed in, so a state handed to ``train_step``, ``step`` or
        ``run`` is consumed (its arrays are deleted) and only the state
        returned may be used. Every leaf of the state must be a buffer of
        its own (``init_state`` makes it so).

        ``obs`` (an ``repro.obs.Obs``) turns on per-step spans (see
        ``step``), counters, and the gauge ``train_state_aliased_share``
        (set on the first step: the share of the compiled step's output
        bytes written into donated input buffers); ``step_wire`` is an
        accounted wire-byte report for one step
        (``dist.accounting.grad_wire_bytes`` / ``dp_step_wire_bytes`` /
        ``fsdp_step_wire_bytes`` output) — its per-leaf entries become
        per-leaf wire counters incremented every step, so the registry
        shows what the collectives actually carry. Both default off; the
        obs-off loop is unchanged."""
        self.train_step = jax.jit(train_step, donate_argnums=0)
        self.cfg = cfg
        self.batch_at = batch_at
        self.checkpointer = (ckpt.AsyncCheckpointer(cfg.ckpt_dir, keep=cfg.keep)
                             if cfg.ckpt_dir else None)
        self.straggler_events: list[tuple[int, float]] = []
        self.step_seconds: list[float] = []   # wall time of each run() step
        self._obs = obs
        self._aliasing_read = False
        if obs is not None:
            self._h_step = obs.histogram(
                "train_step_seconds", "per-step wall time").labels()
            self._c_steps = obs.counter(
                "train_steps_total", "optimizer steps taken").labels()
            self._c_strag = obs.counter(
                "train_straggler_events_total",
                "steps slower than watchdog_factor x running median").labels()
            self._wire_handles: list[tuple[Any, float]] = []
            if step_wire is not None:
                c = obs.counter(
                    "train_wire_bytes_total",
                    "accounted collective wire bytes (per leaf)")
                per_leaf = step_wire.get("per_leaf") or []
                for e in per_leaf:
                    self._wire_handles.append(
                        (c.labels(leaf=e["path"], mode=e["mode"]),
                         float(e["wire_bytes"])))
                accounted = sum(b for _, b in self._wire_handles)
                rest = float(step_wire.get("total_bytes", 0.0)) - accounted
                if rest > 0:  # param gathers / scalar overhead / no-leaf
                    self._wire_handles.append(
                        (c.labels(leaf="_other", mode="aggregate"), rest))

    def resume_or(self, state):
        """Resume from the newest valid checkpoint, else the given state."""
        if self.cfg.ckpt_dir:
            step, restored, _ = ckpt.restore_latest(self.cfg.ckpt_dir, state)
            if restored is not None:
                return restored
        return state

    def step(self, state, batch):
        """One step of the jitted ``train_step``, waiting for its loss.
        With the tracer on it records two ``host`` spans: ``train.dispatch``
        (the jitted call until it returns: argument handling and launch)
        and ``train.wait`` (blocking on the loss). ``state`` is consumed."""
        obs = self._obs
        if obs is not None and not self._aliasing_read:
            self._read_aliasing(obs, state, batch)
        tr = obs.tracer if obs is not None else None
        if tr is None:
            state, metrics = self.train_step(state, batch)
            jax.block_until_ready(metrics["loss"])
            return state, metrics
        t0 = time.monotonic()
        state, metrics = self.train_step(state, batch)
        t1 = time.monotonic()
        jax.block_until_ready(metrics["loss"])
        t2 = time.monotonic()
        tr.complete("train.dispatch", t0, t1 - t0)
        tr.complete("train.wait", t1, t2 - t1)
        return state, metrics

    def _read_aliasing(self, obs, state, batch):
        """Set ``train_state_aliased_share`` from the compiled step (the
        one the call that follows runs, from the same cache)."""
        mem = self.train_step.lower(state, batch).compile().memory_analysis()
        obs.gauge("train_state_aliased_share",
                  "share of the step's output bytes written into donated "
                  "state buffers").set(
            mem.alias_size_in_bytes / mem.output_size_in_bytes)
        self._aliasing_read = True

    def run(self, state, *, fail_at_step: Optional[int] = None):
        """Steps from ``state["step"]`` to ``cfg.num_steps``; ``state`` is
        consumed, the final state returned."""
        cfg = self.cfg
        history = []
        durations = self.step_seconds
        start = int(state["step"])
        tr = self._obs.tracer if self._obs is not None else None
        for step in range(start, cfg.num_steps):
            if fail_at_step is not None and step == fail_at_step:
                raise SimulatedFailure(f"injected failure at step {step}")
            if tr is None:
                batch = self.batch_at(step)
            else:
                with tr.span("train.batch"):
                    batch = self.batch_at(step)
            t0 = time.monotonic()
            state, metrics = self.step(state, batch)
            dt = time.monotonic() - t0
            straggled = False
            if len(durations) >= 5:
                med = statistics.median(durations[-50:])
                if dt > cfg.watchdog_factor * med:
                    self.straggler_events.append((step, dt / med))
                    straggled = True
            durations.append(dt)
            if self._obs is not None:
                self._h_step.observe(dt)
                self._c_steps.inc()
                if straggled:
                    self._c_strag.inc()
                for h, b in self._wire_handles:
                    h.inc(b)
                if tr is not None:
                    tr.complete("train_step", t0, dt, cat="interval",
                                step=step)
            if step % cfg.log_every == 0 or step == cfg.num_steps - 1:
                history.append((step, float(metrics["loss"])))
            if self.checkpointer and (step + 1) % cfg.ckpt_every == 0:
                self.checkpointer.save(step + 1, state)
        if self.checkpointer:
            self.checkpointer.save(cfg.num_steps, state)
            self.checkpointer.wait()
        return state, history
