"""Joining the program's own spans to a profiler trace
(``bench/program_trace.py``): the anchor on a real CPU profile, the idle
attribution and scope split by hand on a small trace, and the train
step's named scopes."""

import re
import time

import jax
import jax.numpy as jnp
import pytest

import bench_fixture as bf  # noqa: F401  (puts the repo and src on the path)
from bench import program_trace as pt
from repro.obs import Tracer
from repro.obs.trace import ANCHOR
from repro.optim.optimizers import adagrad
from repro.train.loop import init_state, make_train_step


def test_anchor_places_program_span_on_the_profiler_clock(tmp_path):
    """A span the tracer records and a ``TraceAnnotation`` opened and
    closed at the same moments land within 50 us of each other."""
    from jax.profiler import ProfileData, TraceAnnotation
    from bench.tracing import find_xplane
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr.anchor()
        time.sleep(0.05)               # the join must not drift with time
        with TraceAnnotation("probe"):
            t0 = time.monotonic()
            time.sleep(0.002)
            t1 = time.monotonic()
        tr.complete("probe", t0, t1 - t0)
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(find_xplane(str(tmp_path)))
    host = pt.host_events(pd)
    assert ANCHOR == pt.ANCHOR
    (_, s, e), = [h for h in host if h[0] == "probe"]
    (_, js, je), = [sp for sp in pt.joined_spans(
        tr.events, pt.anchor_offset_ns(host, tr.events)) if sp[0] == "probe"]
    assert abs(js - s) < 50e3 and abs(je - e) < 50e3
    with pytest.raises(ValueError, match="clock_anchor"):
        pt.anchor_offset_ns(host, [ev for ev in tr.events
                                   if ev["name"] != ANCHOR])


def _plane(pid, name, lines):
    """Text proto of one XPlane; ``lines`` maps a line name to
    ``(event name, start_ns, end_ns)``."""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for lid, (lname, evs) in enumerate(lines.items(), 1):
        out.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0')
        for n, s, e in evs:
            out.append(f"events {{ metadata_id: {meta[n]} offset_ps: {s * 1000}"
                       f" duration_ps: {(e - s) * 1000} }}")
        out.append("}")
    for n, i in meta.items():
        out.append(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}')
    out.append("}")
    return "\n".join(out)


def _event(name, s_ns, e_ns, cat="host"):
    """A tracer event placed so that it lands at ``s_ns .. e_ns`` on the
    trace's clock: the anchor below makes the offset 1000 ns."""
    return {"name": name, "cat": cat, "ph": "X", "pid": 0, "tid": 1,
            "ts": (s_ns - 1000) / 1e3, "dur": (e_ns - s_ns) / 1e3}


def test_joined_reduction_by_hand():
    """In ns on the trace's clock: window 1000..11000; ops 3000..4000
    (``jit_step``, backward), 4000..4500 (``jit_step``, optimizer), both by
    their HLO ``op_name``, 8000..9000 (another program): busy 2500, idle
    1000..3000, 4500..8000, 9000..11000.

    * 1000..2000 no span; 2000..2500 ``bench.engine_step``; 2500..3000
      ``serve.form_wave`` (inside ``bench.engine_step``: the program wins);
    * 4500..6000 ``serve.flush`` (inside ``bench.engine_step``), 6000..7000
      no span, 7000..8000 ``bench.submit``;
    * 9000..9500 no span, 9500..10500 ``train.batch`` less its inner
      ``train.dispatch`` 9700..9900, 10500..11000 no span.

    The two ``wave`` events are ``interval``: they cover idle time that
    no span covers, and must not take it."""
    host = {"python": [("bench.window", 1000, 11000),
                       ("obs.clock_anchor", 1500, 1600),
                       ("bench.engine_step", 2000, 6000),
                       ("bench.submit", 7000, 8000)]}
    dev = {"XLA Modules": [("jit_step(1)", 2900, 4600),
                           ("jit_other(2)", 7900, 9100)],
           "XLA Ops": [("%fusion.1 = f32[] add()", 3000, 4000),
                       ("%fusion.2 = f32[] add()", 4000, 4500),
                       ("%fusion.3 = f32[] add()", 8000, 9000)]}
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(
        _plane(1, "/host:CPU", host) + "\n" + _plane(2, "/device:TPU:0", dev))
    events = [{"name": ANCHOR, "cat": "interval", "ph": "X", "pid": 0,
               "tid": 1, "ts": 0.5, "dur": 0.0},
              _event("serve.form_wave", 2500, 3000),
              _event("serve.flush", 4500, 6000),
              _event("wave", 2000, 7000, cat="interval"),
              _event("wave", 8000, 12000, cat="interval"),
              _event("train.batch", 9500, 10500),
              _event("train.dispatch", 9700, 9900)]
    j = pt.reduce_joined(pd, events, hlo_names={
        "fusion.1": "jit(step)/transpose(jvp(forward))/dot_general",
        "fusion.2": "jit(step)/optimizer/add",
        "fusion.3": "jit(step)/jvp(forward)/add"})
    ns = 1e-9
    assert j.window_s == pytest.approx(10000 * ns)
    assert j.busy_s == pytest.approx(2500 * ns)
    assert dict(j.idle_gaps) == pytest.approx(
        {pt.NO_SPAN: 3000 * ns, "bench.engine_step": 500 * ns,
         "serve.form_wave": 500 * ns, "serve.flush": 1500 * ns,
         "bench.submit": 1000 * ns, "train.batch": 800 * ns,
         "train.dispatch": 200 * ns})
    assert j.program_idle == pytest.approx(
        {"serve.form_wave": 500 * ns, "serve.flush": 1500 * ns,
         "train.batch": 800 * ns, "train.dispatch": 200 * ns})
    assert sum(v for _, v in j.idle_gaps) == pytest.approx(
        j.window_s - j.busy_s)
    assert j.step_scope_s == pytest.approx({"backward": 1000 * ns,
                                            "optimizer": 500 * ns})
    assert j.ends_in_window["wave"] == 1          # the second ends after
    assert j.ends_in_window["serve.flush"] == 1


def _toy():
    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p["w1"])
        err = h @ p["w2"] - b["y"]
        loss = jnp.mean(err ** 2)
        return loss, {"mse": loss}

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    params = {"w1": jax.random.normal(k1, (8, 16)),
              "w2": jax.random.normal(k2, (16, 1))}
    batch = {"x": jnp.ones((4, 8)), "y": jnp.zeros((4, 1))}
    return loss_fn, params, batch


def _strip(hlo: str) -> str:
    """The program without its metadata and source-location tables."""
    hlo = re.sub(r",? (metadata=\{[^}]*\}|stack_frame_id=\d+)", "", hlo)
    return "\n".join(line for line in hlo.splitlines()
                     if not re.match(r'^\s*\d+ ["{]', line))


def test_train_step_scopes_split_forward_backward_optimizer():
    loss_fn, params, batch = _toy()
    opt = adagrad(1e-2)
    state = init_state(params, opt)
    compiled = jax.jit(make_train_step(loss_fn, opt)).lower(state, batch).compile()
    text = compiled.as_text()
    assert re.search(r"HloModule jit_step\b", text)
    names = pt.hlo_op_names(text)
    scopes = {pt.scope_of(v) for v in names.values()}
    assert {"forward", "backward", "optimizer"} <= scopes
    markers = ("transpose(jvp(forward))", "jvp(forward)", "optimizer")
    for op_name in names.values():          # nothing under two scopes
        assert sum(m in op_name.split("/") for m in markers) <= 1, op_name

    def step(state, batch):                 # the same step, no scopes
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"], batch)
        p, o = opt.update(grads, state["opt"], state["params"], state["step"])
        return ({"params": p, "opt": o, "step": state["step"] + 1},
                dict(metrics, loss=loss))

    plain = jax.jit(step).lower(state, batch).compile().as_text()
    assert {pt.scope_of(v) for v in pt.hlo_op_names(plain).values()} \
        == {"other"}
    assert _strip(plain) == _strip(text)    # metadata is all they add
