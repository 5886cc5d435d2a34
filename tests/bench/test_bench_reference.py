"""The benchmark's plain references against the program, on the CPU at the
program's reduced sizes, and the counts its metrics divide by."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_fixture as bf
from bench import generator, harness, spec
from bench import serve as sv
from bench.metrics import _counts
from bench.reference import api as ref

SEED = 2 ** 31 + 77


def config(name: str) -> dict:
    cfg = json.loads((bf.REPO / "bench" / "configs" / f"{name}.json").read_text())
    cfg["program"]["reduced_sizes"] = True
    cfg["model"]["table_sizes"] = bf.REDUCED_SIZES
    if "serve" in cfg:
        cfg["serve"]["max_batch"] = 8
    cfg["program_cfg"], cfg["program_api"] = harness.program_config(cfg)
    return cfg


def served(cfg, mix, n=24):
    """Scores the engine gives ``n`` requests of ``mix``, and the requests."""
    params = ref.make_params(SEED, cfg["model"])
    engine = sv.build_engine(cfg, params)
    reqs = spec.process("open_poisson").requests(mix, cfg["model"], SEED,
                                                 generator.WINDOW, 1.0, rate=n)
    uids = [engine.submit(reqs.dense[i], reqs.bags(i)) for i in range(n)]
    done = engine.run_until_drained()
    return np.asarray([done[u].score for u in uids]), reqs, params


MIXES = {"multihot": {"bag_lengths": bf.MULTIHOT, "skew": 1.5},
         "onehot": {"bag_lengths": None, "skew": 1.5}}


@pytest.mark.parametrize("name,mix", [("dlrm-criteo-kaggle", "multihot"),
                                      ("dcn-criteo-kaggle", "onehot")])
def test_engine_scores_match_reference_and_control_fails(name, mix):
    cfg = config(name)
    got, reqs, params = served(cfg, MIXES[mix])
    rows = np.arange(len(got))
    want = ref.serve_logits(ref.quantize_tables(params), *reqs.padded(rows),
                            cfg["model"])
    assert harness.gap_ratio(got, want) < 1e-5
    # the control, int4 tables and a bfloat16 dense half, reads thousands
    # of times further off.  (Against the committed limit it is read on the
    # chip at the cell's size, where it reads 0.33-0.56: PERF.md; the
    # reduced tables here read lower.)
    ctl = ref.serve_logits(ref.quantize_tables(params, bits=4),
                           *reqs.padded(rows), cfg["model"], dtype=jnp.bfloat16)
    assert harness.gap_ratio(ctl, want) > 0.03
    # an answer altered where it is produced fails the committed limit
    limit = json.loads((bf.REPO / "bench" / "configs" / f"{name}.json")
                       .read_text())["limits"]["score_gap"]
    bad = got.copy()
    bad[3] = got[np.argmax(np.abs(got - got[3]))]   # another request's score
    assert harness.gap_ratio(bad, want) > limit


@pytest.mark.parametrize("name", ["dlrm-criteo-kaggle", "dcn-criteo-kaggle"])
def test_trainer_steps_match_reference_and_bf16_fails(name):
    from repro.train.loop import TrainConfig, Trainer, init_state, make_train_step
    cfg = config(name)
    model, opt, api = cfg["model"], cfg["train"], cfg["program_api"]
    make_batch = spec.process("device_batches").batch_fn(
        {"batch": 64, "skew": 1.5, "label_noise": 0.5}, model, SEED)
    params0 = ref.make_params(SEED, model)
    state = init_state(params0, api.optimizer)
    trainer = Trainer(make_train_step(api.loss_fn, api.optimizer),
                      TrainConfig(num_steps=0), batch_at=make_batch)
    from bench.reference.common import first_grad_norms, leaf_norms
    losses = []
    for t in range(3):
        state, met = trainer.train_step(state, make_batch(t))
        losses.append(float(met["loss"]))
        if t == 0:
            first = first_grad_norms(opt, state["opt"], state["params"])
            change1 = leaf_norms(jax.tree.map(lambda a, b: a - b,
                                              state["params"], params0))
    change = leaf_norms(jax.tree.map(lambda a, b: a - b, state["params"], params0))
    batches = [make_batch(t) for t in range(3)]
    want = ref.train_steps(params0, batches, model, opt)
    limits = json.loads((bf.REPO / "bench" / "configs" / f"{name}.json")
                        .read_text())["limits"]
    got = harness.train_numbers({"losses": losses, "first_grad": first,
                                 "change1": change1, "change": change}, want)
    assert all(v < 1e-4 for v in got.values()), got
    ctl = harness.compared(harness.train_numbers(
        ref.train_steps(params0, batches, model, opt, dtype=jnp.bfloat16),
        want), limits)
    assert any(v > lim for v, lim in ctl.values()), ctl


def test_dp_control_fails_the_committed_limits():
    """The four-chip cell's controls against its reference (the int8
    exchange over four shards of the batch), at reduced size: the
    reference in bfloat16, and the reference with its exchange one
    precision lower, in int4, each fail a committed limit.  (On the chip
    at the cell's size: PERF.md.)"""
    cfg = config("dlrm-criteo-kaggle-dp4")
    model, opt = cfg["model"], cfg["train"]
    make_batch = spec.process("device_batches").batch_fn(
        {"batch": 64, "skew": 1.5, "label_noise": 0.5}, model, SEED)
    batches = [make_batch(t) for t in range(3)]
    params0 = ref.make_params(SEED, model)
    want = ref.train_steps(params0, batches, model, opt, replicas=4)
    limits = json.loads((bf.REPO / "bench" / "configs"
                         / "dlrm-criteo-kaggle-dp4.json").read_text())["limits"]
    for kw in ({"dtype": jnp.bfloat16}, {"bits": 4}):
        ctl = harness.compared(harness.train_numbers(
            ref.train_steps(params0, batches, model, opt, replicas=4, **kw),
            want), limits)
        assert any(v > lim for v, lim in ctl.values()), (kw, ctl)


def test_counts_by_hand():
    model = {"family": "dlrm", "dense_dim": 2, "table_sizes": [5, 9],
             "emb_dim": 2, "num_collisions": 4, "bottom_mlp": [3],
             "top_mlp": [4]}
    # bottom 2-3-2: 2*(6+6)=24; 3 vectors, 3 pairs of 2-wide dots: 12;
    # top (3+2)-4-1: 2*(20+4)=48
    assert _counts.forward_flops(model) == 84
    dcn = {"family": "dcn", "dense_dim": 2, "table_sizes": [5, 9],
           "emb_dim": 2, "cross_layers": 2, "deep_mlp": [3]}
    # x0 = 2 + 2*2 = 6; cross 2*5*6 = 60; deep 6-3: 36; out (6+3)-1: 18
    assert _counts.forward_flops(dcn) == 114
    # S=9, c=4: m=3 remainder rows; ids 0,1,4,8 -> remainders {0,1,2},
    # quotients {0,1,2}: 6 rows
    assert _counts.touched_rows(model, 1, np.array([0, 1, 4, 8, 4])) == 6
    # S=5: m=2; ids {0,3}: remainders {0,1}, quotients {0,1}: 4 rows
    ids = np.array([[0, 0, 4], [3, 8, 8]])    # per request: f0 1 id, f1 2
    # f0 ids {0,3}: 4 rows; f1 ids {0,4,8}: remainders {0,1,2}, quotients
    # {0,1,2}: 6 rows; 10 rows * (2+3) B + 2 requests * 2 feats * 2 * 4 B
    assert _counts.embed_min_bytes(model, ids, [1, 2]) == 10 * 5 + 32
    # dense params: bottom 2*3+3 + 3*2+2 = 17; top 5*4+4 + 4*1+1 = 29
    assert _counts.dense_param_count(model) == 46
    sparse = np.array([[0, 0], [3, 4]])       # f0 {0,3}: 4 rows; f1 {0,4}: 4
    # Adagrad: 4 B * (2 + 2 + 2) per value; (8 rows * 2 + 46) values
    assert _counts.train_step_min_bytes(model, {"name": "adagrad"},
                                        sparse) == 24 * 62
    assert _counts.train_step_min_bytes(model, {"name": "amsgrad"},
                                        sparse) == 40 * 62


def test_bag_batches_train_as_their_live_slots():
    """A bag batch with one live slot a bag trains as the one-hot batch;
    ids under a zero mask change nothing."""
    model, opt = config("dlrm-criteo-kaggle")["model"], {"name": "adagrad",
                                                          "lr": 1e-2, "eps": 1e-10}
    make_batch = spec.process("device_batches").batch_fn(
        {"batch": 32, "skew": 1.5, "label_noise": 0.5}, model, SEED)
    onehot = [jax.device_get(make_batch(t)) for t in range(2)]
    rng = np.random.default_rng(SEED)
    sizes = np.asarray(model["table_sizes"])

    def bags(b, pad):
        n, f = b["sparse"].shape
        junk = (rng.random((n, f, pad)) * sizes[:, None]).astype(np.int32)
        return dict(b, sparse=np.concatenate([b["sparse"][..., None], junk], -1),
                    mask=np.concatenate([np.ones((n, f, 1), np.float32),
                                         np.zeros((n, f, pad), np.float32)], -1))
    params0 = ref.make_params(SEED, model)
    want = ref.train_steps(params0, onehot, model, opt)
    for pad in (0, 3):
        got = ref.train_steps(params0, [bags(b, pad) for b in onehot], model, opt)
        for k in want:
            np.testing.assert_allclose(got[k], want[k], rtol=1e-6, atol=1e-9)


def test_counts_of_the_kaggle_configurations_are_pinned():
    """The counts the metrics divide by, for both Kaggle configurations:
    one example's forward FLOPs, the dense parameters, and a training
    step's least bytes on a batch drawn from a fixed seed."""
    rng = np.random.default_rng(20261019)
    u = rng.random((2048, 26))
    pinned = {"dlrm-criteo-kaggle": (959968, 475985, 20469912),
              "dcn-criteo-kaggle": (748064, 373578, 30020240)}
    for name, want in pinned.items():
        cfg = json.loads((bf.REPO / "bench" / "configs" / f"{name}.json")
                         .read_text())
        model = cfg["model"]
        sizes = np.asarray(model["table_sizes"])
        sparse = np.minimum(np.floor(u ** 1.5 * sizes).astype(np.int64),
                            sizes - 1)
        got = (_counts.forward_flops(model), _counts.dense_param_count(model),
               _counts.train_step_min_bytes(model, cfg["train"], sparse))
        assert got == want, name
        # the same batch as one-slot bags, with masked slots that would
        # touch other rows, counts the same
        pad = np.stack([sparse, np.broadcast_to(sizes - 1, sparse.shape)], -1)
        mask = np.concatenate([np.ones_like(sparse)[..., None],
                               np.zeros_like(sparse)[..., None]], -1)
        assert _counts.train_step_min_bytes(model, cfg["train"], pad,
                                            mask) == want[2]
        # and as the list form, one array of width 1 a feature
        assert _counts.train_step_min_bytes(
            model, cfg["train"], list(sparse.T[:, :, None])) == want[2]


def test_exchange_bytes_by_hand():
    model = {"family": "dlrm", "dense_dim": 2, "table_sizes": [5, 9],
             "emb_dim": 2, "num_collisions": 4, "bottom_mlp": [3],
             "top_mlp": [4]}
    # tables: S=5 -> 2+3 rows, S=9 -> 3+3 rows, 11 rows * 2 = 22 elements;
    # 46 dense parameters; 68 elements, 2*(4-1)/4 of them a chip sends
    assert _counts.exchange_min_bytes(model, "int8", 4) == 102
    assert _counts.grad_elements(model) == 68
    # each replica's f32 residual of every element, read and written
    assert _counts.error_state_min_bytes(model) == 544
    assert _counts.exchange_min_bytes(model, "int8", 1) == 0


def test_unknown_family_has_no_counts():
    with pytest.raises(ModuleNotFoundError):
        _counts.forward_flops({"family": "no_such_family", "table_sizes": [5]})


@pytest.mark.parametrize("name,key", [("dlrm-criteo-kaggle", "top_mlp"),
                                      ("dlrm-criteo-kaggle", "bottom_mlp"),
                                      ("dcn-criteo-kaggle", "cross_layers"),
                                      ("dcn-criteo-kaggle", "deep_mlp")])
def test_program_config_refuses_a_width_it_does_not_run(name, key):
    cfg = json.loads((bf.REPO / "bench" / "configs" / f"{name}.json").read_text())
    harness.program_config(cfg)
    v = cfg["model"][key]
    cfg["model"][key] = [*v[:-1], v[-1] * 2] if isinstance(v, list) else v + 1
    with pytest.raises(ValueError, match="differs"):
        harness.program_config(cfg)


@pytest.mark.parametrize("chips,compress,ok", [(4, "int8", True),
                                               (1, None, True),
                                               (4, None, False),
                                               (1, "int8", False)])
def test_train_cell_chips_and_compression_go_together(chips, compress, ok):
    """A cell on several chips runs the data-parallel step and needs a
    compression policy; a one-chip cell runs the plain step and has none."""
    cfg = {"train": {"name": "adagrad"}}
    if compress:
        cfg["train"]["compress"] = compress
    cell = {"name": "x.y", "chips": chips}
    if ok:
        assert harness.dp_compress(cell, cfg) == compress
    else:
        with pytest.raises(ValueError, match="compress"):
            harness.dp_compress(cell, cfg)


def test_int_exchange_keeps_what_it_drops():
    """The reference's integer exchange: the mean it gives is within half
    a step of each code of the true mean, and the errors it hands on hold
    exactly what it dropped, so the replicas' errors plus ``n`` times the
    mean add up to the replicas' gradients (error feedback at both
    levels telescopes)."""
    from bench.reference.common import int_exchange
    rng = np.random.default_rng(SEED)
    g = [jnp.asarray(rng.standard_normal((4, 7, 3)) * rng.random((1, 7, 1)),
                     jnp.float32),
         jnp.asarray(rng.standard_normal((4, 5)), jnp.float32)]
    e = [jnp.zeros_like(x) for x in g]
    for bits in (8, 4):
        out, err = int_exchange(g, e, bits)
        for x, o, r in zip(g, out, err):
            qmax = 2 ** (bits - 1) - 1
            step1 = float(jnp.max(jnp.abs(x))) / qmax
            assert np.all(np.abs(np.asarray(o) - np.asarray(x).mean(0))
                          <= step1 / 2 + float(jnp.max(jnp.abs(o))) / qmax / 2
                          + 1e-6)
            np.testing.assert_allclose(np.asarray(r).sum(0) + 4 * np.asarray(o),
                                       np.asarray(x).sum(0), atol=1e-5)


# ---------------------------------------------- list-form bags, blocks, row-wise

ROWWISE = {"name": "adagrad", "tables": "rowwise", "lr": 1e-2, "eps": 1e-10}


def list_batches(model, n=3, batch=64, seed=SEED):
    """``n`` list-form batches of ``device_bags`` in the reduced bag widths."""
    make_batch = spec.process("device_bags").batch_fn(
        {"batch": batch, "bag_lengths": bf.MULTIHOT, "skew": 1.5,
         "label_noise": 0.5}, model, seed)
    return [jax.device_get(make_batch(t)) for t in range(n)]


def padded(b):
    """The list-form batch ``b`` as ``(B, F, L)`` bags padded to the widest
    with masked slots of id 0 (in ``jax.numpy``, so a jitted loss can pad
    too)."""
    widths = np.asarray([x.shape[1] for x in b["sparse"]])
    lmax = int(widths.max())
    idx = jnp.stack([jnp.pad(x, ((0, 0), (0, lmax - x.shape[1])))
                     for x in b["sparse"]], axis=1)
    mask = np.arange(lmax)[None, :] < widths[:, None]
    return dict(b, sparse=idx, mask=jnp.broadcast_to(
        jnp.asarray(mask, jnp.float32), idx.shape))


def assert_same_steps(got, want, rtol):
    for k in got:
        np.testing.assert_allclose(got[k], want[k], rtol=rtol, atol=0, err_msg=k)


@pytest.mark.parametrize("opt", [None, ROWWISE], ids=["adagrad", "rowwise"])
@pytest.mark.parametrize("block", [None, 16], ids=["one_block", "blocks_of_16"])
def test_list_form_trains_as_the_same_bags_padded(opt, block):
    """Fixed-width bags in the list form give the loss, first gradient and
    change of the first step of the same bags padded to the widest with a
    ``mask``, to 1e-6; the pooled sums round apart in the last bit, which
    three steps compound, so over three steps to 1e-5."""
    cfg = config("dlrm-criteo-kaggle")
    model, opt = cfg["model"], opt or cfg["train"]
    batches = list_batches(model)
    params0 = ref.make_params(SEED, model)
    got = ref.train_steps(params0, batches, model, opt, block=block)
    want = ref.train_steps(params0, [padded(b) for b in batches], model, opt,
                           block=block)
    first = ("first_grad", "change1")
    assert_same_steps({k: got[k] for k in first}, want, rtol=1e-6)
    np.testing.assert_allclose(got["losses"][0], want["losses"][0], rtol=1e-6)
    assert_same_steps(got, want, rtol=1e-5)


def _batches(form, model):
    if form == "list":
        return list_batches(model)
    make_batch = spec.process("device_batches").batch_fn(
        {"batch": 64, "skew": 1.5, "label_noise": 0.5}, model, SEED)
    onehot = [jax.device_get(make_batch(t)) for t in range(3)]
    if form == "onehot":
        return onehot
    rng = np.random.default_rng(SEED)     # bags of 1-4 live slots of 4
    return [dict(b, sparse=np.concatenate(
        [b["sparse"][..., None],
         (rng.random(b["sparse"].shape + (3,)) * np.asarray(bf.REDUCED_SIZES)
          [:, None]).astype(np.int32)], -1),
        mask=(np.arange(4) <= rng.integers(0, 4, b["sparse"].shape + (1,)))
        .astype(np.float32)) for b in onehot]


@pytest.mark.parametrize("name,form,block", [
    ("dlrm-criteo-kaggle", "onehot", 16), ("dlrm-criteo-kaggle", "bags", 16),
    ("dlrm-criteo-kaggle", "list", 16), ("dcn-criteo-kaggle", "onehot", 16),
    ("dcn-criteo-kaggle", "list", 24)])
def test_blocks_match_one_block(name, form, block):
    """A batch of 64 in blocks of ``block`` (24 leaves a short last block)
    gives what one block gives, to f32 summation order: the blocked
    gradient is taken with respect to the rows a block reads and added
    into the tables, the one block's through the whole tables."""
    cfg = config(name)
    model, opt = cfg["model"], cfg["train"]
    batches = _batches(form, model)
    params0 = ref.make_params(SEED, model)
    want = ref.train_steps(params0, batches, model, opt, block=64)
    got = ref.train_steps(params0, batches, model, opt, block=block)
    assert_same_steps(got, want, rtol=1e-5)


@pytest.mark.parametrize("form", ["onehot", "list"])
def test_blocks_within_replica_shards_read_as_one_block(form):
    """Under the int8 exchange each of the four replicas' shards of 16 is
    taken in blocks of 8.  Summation order can flip an int8 code between 0
    and 1, which Adagrad turns into a whole step of ``lr`` for that
    element, so the steps are not equal to 1e-5; the numbers ``correct``
    compares read the blocked reference against the one-block one within
    a tenth of each committed limit, and the first loss to f32 order."""
    cfg = config("dlrm-criteo-kaggle-dp4")
    model, opt = cfg["model"], cfg["train"]
    batches = _batches(form, model)
    params0 = ref.make_params(SEED, model)
    want = ref.train_steps(params0, batches, model, opt, replicas=4, block=16)
    got = ref.train_steps(params0, batches, model, opt, replicas=4, block=8)
    np.testing.assert_allclose(got["losses"][0], want["losses"][0], rtol=1e-6)
    limits = json.loads((bf.REPO / "bench" / "configs"
                         / "dlrm-criteo-kaggle-dp4.json").read_text())["limits"]
    for k, (v, lim) in harness.compared(harness.train_numbers(got, want),
                                        limits).items():
        assert v <= lim / 10, (k, v, lim)


@pytest.mark.parametrize("form", ["onehot", "list"])
def test_a_batch_within_one_block_is_bitwise_unblocked(form):
    """A batch of at most ``block`` examples runs as one block: its
    results are bit for bit those of the default block."""
    cfg = config("dlrm-criteo-kaggle")
    model, opt = cfg["model"], cfg["train"]
    batches = _batches(form, model)
    params0 = ref.make_params(SEED, model)
    want = ref.train_steps(params0, batches, model, opt)
    got = ref.train_steps(params0, batches, model, opt, block=64)
    for k in want:
        assert np.array_equal(got[k], want[k]), k
    assert ref.BLOCK == 2048


@pytest.mark.parametrize("form", ["onehot", "list"])
def test_rowwise_adagrad_matches_the_programs_partitioned_optimizer(form):
    """The reference's row-wise Adagrad against the program's
    ``partitioned([(tables, rowwise_adagrad)], adagrad)`` over three
    steps, the program's first gradient read back from its row-wise state.
    The program's loss takes the list form padded with a mask."""
    from repro.models.dlrm import dlrm_forward
    from repro.optim.optimizers import adagrad, partitioned, rowwise_adagrad
    from repro.train.loop import TrainConfig, Trainer, init_state, make_train_step
    from bench.reference.common import bce_with_logits, first_grad_norms, leaf_norms
    cfg = config("dlrm-criteo-kaggle")
    model, pcfg = cfg["model"], cfg["program_cfg"]
    optimizer = partitioned([(lambda path: "tables" in path,
                              rowwise_adagrad(ROWWISE["lr"], ROWWISE["eps"]))],
                            adagrad(ROWWISE["lr"], ROWWISE["eps"]))

    def loss_fn(p, b):
        if isinstance(b["sparse"], list):
            b = padded(b)
        logits = dlrm_forward(p, b["dense"], b["sparse"], pcfg,
                              mask=b.get("mask"))
        return bce_with_logits(logits, b["label"]), {}
    batches = _batches(form, model)
    params0 = ref.make_params(SEED, model)
    state = init_state(params0, optimizer)
    trainer = Trainer(make_train_step(loss_fn, optimizer),
                      TrainConfig(num_steps=0), batch_at=None)
    losses = []
    for t, b in enumerate(batches):
        state, met = trainer.train_step(state, b)
        losses.append(float(met["loss"]))
        if t == 0:
            first = first_grad_norms(ROWWISE, state["opt"], state["params"])
            change1 = leaf_norms(jax.tree.map(lambda a, b: a - b,
                                              state["params"], params0))
    change = leaf_norms(jax.tree.map(lambda a, b: a - b, state["params"], params0))
    want = ref.train_steps(params0, batches, model, ROWWISE)
    got = harness.train_numbers({"losses": losses, "first_grad": first,
                                 "change1": change1, "change": change}, want)
    assert all(v < 1e-4 for v in got.values()), got
    # element-wise Adagrad on the tables is another optimizer (its first
    # step moves a touched row as far, so only the later steps tell it)
    other = harness.train_numbers(ref.train_steps(
        params0, batches, model, dict(ROWWISE, tables=None)), want)
    assert other["change_gap"] > 0.1, other


@pytest.mark.parametrize("opt", [{"name": "adagrad", "lr": 0.1, "eps": 1e-10},
                                 ROWWISE,
                                 {"name": "amsgrad", "lr": 0.1, "eps": 1e-8,
                                  "b1": 0.9, "b2": 0.999}],
                         ids=["adagrad", "rowwise", "amsgrad"])
def test_first_grad_norms_read_the_gradient_back_from_the_state(opt):
    """After one step from zero state the optimizer's state gives each
    leaf's gradient norm back: a row-wise accumulator holds a row's mean
    of ``g**2``, one value for a table row of ``D``."""
    from bench.reference.common import (first_grad_norms, leaf_norms,
                                        opt_init, opt_update)
    rng = np.random.default_rng(SEED)
    shapes = {"tables": [{"table_0": (7, 3), "table_1": (4, 3)}],
              "top": [{"b": (2,), "w": (3, 2)}]}
    params = jax.tree.map(lambda s: jnp.asarray(rng.standard_normal(s),
                                                jnp.float32), shapes,
                          is_leaf=lambda x: isinstance(x, tuple))
    grads = jax.tree.map(lambda x: jnp.asarray(
        rng.standard_normal(x.shape), jnp.float32), params)
    state = opt_init(opt, params, jnp.float32)
    rows = opt.get("tables") == "rowwise"
    assert [s[next(iter(s))].shape for s in state] == [
        (7, 1) if rows else (7, 3), (4, 1) if rows else (4, 3), (2,), (3, 2)]
    _, state = opt_update(opt, grads, state, params, 0)
    np.testing.assert_allclose(first_grad_norms(opt, state, params),
                               leaf_norms(grads), rtol=1e-6)


def test_rowwise_is_adagrad_only():
    from bench.reference.common import rowwise
    assert rowwise(ROWWISE) and not rowwise({"name": "amsgrad"})
    for bad in ({"name": "amsgrad", "tables": "rowwise"},
                {"name": "adagrad", "tables": "columnwise"}):
        with pytest.raises(ValueError, match="row-wise"):
            rowwise(bad)


def test_list_form_and_rowwise_counts_by_hand():
    model = {"family": "dlrm", "dense_dim": 2, "table_sizes": [5, 9],
             "emb_dim": 2, "num_collisions": 4, "bottom_mlp": [3],
             "top_mlp": [4]}
    # f0, one id an example: ids {0,3}, S=5, m=2: 4 rows; f1, two:
    # ids {0,4,8}, S=9, m=3: remainders {0,1,2}, quotients {0,1,2}: 6 rows
    sparse = [np.array([[0], [3]]), np.array([[0, 4], [8, 4]])]
    assert _counts.touched_rows(model, 1, sparse[1]) == 6
    # 10 rows of 2 and 46 dense parameters, each with its gradient and
    # Adagrad accumulator, read and written: 24 B a value
    assert _counts.train_step_min_bytes(model, {"name": "adagrad"},
                                        sparse) == 24 * (20 + 46)
    # row-wise: a table row moves its 2 values and gradients (16 B each)
    # and one accumulator (8 B); the dense parameters as before
    assert _counts.train_step_min_bytes(model, ROWWISE, sparse) == \
        10 * (16 * 2 + 8) + 24 * 46
    # the same bags padded to width 2, the pad's ids under a zero mask
    pad = np.array([[[0, 8], [0, 4]], [[3, 8], [8, 4]]])
    mask = np.array([[[1, 0], [1, 1]], [[1, 0], [1, 1]]])
    for train in ({"name": "adagrad"}, ROWWISE):
        assert _counts.train_step_min_bytes(model, train, pad, mask) == \
            _counts.train_step_min_bytes(model, train, sparse)


def test_device_bags_load_by_name_and_repeat_from_seed_and_step():
    """``device_bags`` is found by its name; its batches are the list form
    in the mix's widths, a function of ``(seed, step)`` alone."""
    model = config("dlrm-criteo-kaggle")["model"]
    mix = {"batch": 32, "bag_lengths": bf.MULTIHOT, "skew": 1.5,
           "label_noise": 0.5}
    proc = spec.process("device_bags")
    assert proc.ENTRY == "train"
    b = jax.device_get(proc.batch_fn(mix, model, SEED)(5))
    assert set(b) == {"dense", "sparse", "label"}
    assert [x.shape for x in b["sparse"]] == [(32, n) for n in bf.MULTIHOT]
    for x, s in zip(b["sparse"], model["table_sizes"]):
        assert x.dtype == np.int32 and 0 <= x.min() and x.max() < s
    assert 0 < b["label"].mean() < 1
    again = jax.device_get(spec.process("device_bags").batch_fn(
        mix, model, SEED)(5))
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(b), jax.tree.leaves(again)))
    for other in (proc.batch_fn(mix, model, SEED)(6),
                  proc.batch_fn(mix, model, SEED + 1)(5)):
        assert not np.array_equal(np.asarray(other["sparse"][0]), b["sparse"][0])
    # a 40M-row table: above 2**25 float32 holds every 4th id; the draw
    # reaches the others too
    big = np.asarray(proc.batch_fn(
        dict(mix, bag_lengths=[64]), {"table_sizes": [40_000_000],
                                      "dense_dim": 2}, SEED)(0)["sparse"][0])
    assert big.max() < 40_000_000
    assert set(big[big > 2 ** 25] % 4) == {0, 1, 2, 3}
