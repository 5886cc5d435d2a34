"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dlrm_interact, qr_lookup, serve_bag_pool
from repro.kernels import ref

DTYPES = [jnp.float32, jnp.bfloat16]
TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


def _tables(key, m, q, d, dtype):
    k1, k2 = jax.random.split(key)
    return (jax.random.normal(k1, (m, d), dtype),
            jax.random.normal(k2, (q, d), dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("m,q,d,n", [(7, 3, 16, 5), (128, 8, 128, 64),
                                     (33, 5, 256, 17), (1000, 4, 32, 200)])
@pytest.mark.parametrize("op", ["mult", "add"])
def test_qr_gather_sweep(dtype, m, q, d, n, op):
    wr, wq = _tables(jax.random.PRNGKey(0), m, q, d, dtype)
    idx = jax.random.randint(jax.random.PRNGKey(1), (n,), 0, m * q)
    got = qr_lookup(idx, wr, wq, op=op)
    want = ref.qr_gather_ref(idx % m, idx // m, wr, wq, op=op)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("b,l,m,q,d", [(4, 3, 11, 4, 16), (8, 16, 64, 8, 128),
                                       (3, 7, 29, 5, 64)])
def test_qr_bag_sweep(dtype, b, l, m, q, d):
    wr, wq = _tables(jax.random.PRNGKey(2), m, q, d, dtype)
    idx = jax.random.randint(jax.random.PRNGKey(3), (b, l), 0, m * q)
    mask = (jax.random.uniform(jax.random.PRNGKey(4), (b, l)) > 0.3).astype(dtype)
    got = serve_bag_pool(idx, mask, wr, wq, op="mult")
    want = ref.qr_embedding_bag_ref(idx % m, idx // m, mask, wr, wq, op="mult")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("b,f,d", [(4, 27, 16), (13, 5, 32), (8, 27, 64), (1, 3, 8)])
def test_dot_interaction_sweep(dtype, b, f, d):
    x = jax.random.normal(jax.random.PRNGKey(5), (b, f, d), dtype)
    got = dlrm_interact(x)
    want = ref.dot_interaction_ref(x)
    assert got.shape == (b, f * (f - 1) // 2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_qr_lookup_multidim_indices():
    wr, wq = _tables(jax.random.PRNGKey(6), 10, 10, 8, jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(7), (2, 3, 4), 0, 100)
    got = qr_lookup(idx, wr, wq)
    assert got.shape == (2, 3, 4, 8)
    want = ref.qr_gather_ref(idx % 10, idx // 10, wr, wq)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_concat_falls_back_to_ref():
    wr, wq = _tables(jax.random.PRNGKey(8), 10, 10, 8, jnp.float32)
    idx = jnp.arange(20)
    got = qr_lookup(idx, wr, wq, op="concat")
    assert got.shape == (20, 16)
    np.testing.assert_allclose(got[:, :8], wr[idx % 10], rtol=1e-6)


# ----------------------------------------------------- accumulation audit
#
# The embedding-bag kernel audit found bf16 accumulation diverging from the
# f32 oracle at L=16, D=128 (ROADMAP).  These tests pin the convention for
# every pooling path: combine/accumulate in f32, round once at the end.
# Tolerances are set so a bf16 running sum (one rounding per add, worst case
# ~L·2⁻⁹ relative) fails while a single final cast (2⁻⁹) passes.

AUDIT_B, AUDIT_L, AUDIT_D = 8, 16, 128


def _audit_f32_oracle(idx, mask, wr, wq, op):
    rows_r = jnp.take(wr.astype(jnp.float32), idx % wr.shape[0], axis=0)
    rows_q = jnp.take(wq.astype(jnp.float32), idx // wr.shape[0], axis=0)
    rows = rows_r * rows_q if op == "mult" else rows_r + rows_q
    return (rows * mask[..., None].astype(jnp.float32)).sum(axis=1)


@pytest.mark.parametrize("op", ["mult", "add"])
@pytest.mark.parametrize("use_kernel", [True, False])
def test_bag_accumulates_f32_at_L16_D128(op, use_kernel):
    m, q = 64, 8
    wr, wq = _tables(jax.random.PRNGKey(10), m, q, AUDIT_D, jnp.bfloat16)
    # positive rows: no cancellation, so the running sum grows and bf16
    # accumulation error compounds past the tolerance below
    wr, wq = jnp.abs(wr) + 0.5, jnp.abs(wq) + 0.5
    idx = jax.random.randint(jax.random.PRNGKey(11), (AUDIT_B, AUDIT_L), 0, m * q)
    mask = jnp.ones((AUDIT_B, AUDIT_L), jnp.bfloat16)
    got = serve_bag_pool(idx, mask, wr, wq, op=op, use_kernel=use_kernel)
    want = _audit_f32_oracle(idx, mask, wr, wq, op)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=5e-3, atol=0)


def test_bag_concat_accumulates_f32_at_L16_D128():
    m, q = 64, 8
    wr, wq = _tables(jax.random.PRNGKey(12), m, q, AUDIT_D, jnp.bfloat16)
    wr, wq = jnp.abs(wr) + 0.5, jnp.abs(wq) + 0.5
    idx = jax.random.randint(jax.random.PRNGKey(13), (AUDIT_B, AUDIT_L), 0, m * q)
    mask = jnp.ones((AUDIT_B, AUDIT_L), jnp.bfloat16)
    got = serve_bag_pool(idx, mask, wr, wq, op="concat")
    rows = jnp.concatenate([jnp.take(wr.astype(jnp.float32), idx % m, axis=0),
                            jnp.take(wq.astype(jnp.float32), idx // m, axis=0)],
                           axis=-1)
    want = rows.sum(axis=1)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=5e-3, atol=0)


def test_qr_gather_combines_f32_bf16_tables():
    """Single-row combine: the only rounding is the final cast back to bf16."""
    m, q = 64, 8
    wr, wq = _tables(jax.random.PRNGKey(14), m, q, AUDIT_D, jnp.bfloat16)
    idx = jax.random.randint(jax.random.PRNGKey(15), (AUDIT_L,), 0, m * q)
    got = qr_lookup(idx, wr, wq, op="mult")
    want = (jnp.take(wr.astype(jnp.float32), idx % m, axis=0)
            * jnp.take(wq.astype(jnp.float32), idx // m, axis=0))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=5e-3, atol=1e-6)


def test_kernel_grad_path():
    """Kernels participate in autodiff (interpret mode lowers to jnp ops)."""
    wr, wq = _tables(jax.random.PRNGKey(9), 10, 10, 8, jnp.float32)
    idx = jnp.arange(10)

    def loss(wr, wq):
        return (qr_lookup(idx, wr, wq, use_kernel=False) ** 2).sum()

    g1, g2 = jax.grad(loss, argnums=(0, 1))(wr, wq)
    assert np.isfinite(np.asarray(g1)).all() and np.isfinite(np.asarray(g2)).all()
