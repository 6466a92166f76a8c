"""ops/gated_delta.py on the CPU, float32, seeded: the chunked prefill form
and the in-place decode step against the per-token recurrence (the
definition), from a zero and from a random state, at lengths that are and
are not whole chunks; the causal convolution across a chunk boundary and
from a restored tail.

Tolerances: everything is float32 with `highest` matmuls, and the three
forms differ only in the order of their sums — 2e-5 absolute on outputs and
states of magnitude ~1 is a few hundred float32 roundings, what the 64-row
triangular inverse and the chunk-to-chunk carry leave (the product form over
a whole chunk lost 1e-4 to 1e-2 here: see `_unit_lower_inverse`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_delta as gd

H, DK, DV = 3, 8, 16
TOL = 2e-5
# keys nearly equal through a chunk and beta near 2: the 8-row leaves of the
# triangular inverse hold N^4 with entries of a few hundred, so a float32
# rounding there is 3e-5 absolute (the whole-chunk product lost 1e-2)
TOL_ALIKE = 1e-4
REC = jax.jit(gd.recurrence)
CHK = jax.jit(gd.chunked)


def _draw(seed, batch, length, alike=False):
    """q, k, v, g, beta, state as a linear layer would hand them over.
    `alike`: the keys of neighbouring tokens nearly equal (what a small
    model's do) — beta k_i.k_j near 2, the triangular system's hard case."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    c = jax.random.normal(ks[0], (batch, length, H * (2 * DK + DV)))
    if alike:
        c = c[:, :1] + 0.05 * c
    q, k, v = gd.split_heads(c, H, DK, DV)
    a = jax.random.normal(ks[1], (batch, length, H))
    b = jax.random.normal(ks[2], (batch, length, H)) + (3.0 if alike else 0.0)
    a_log = jnp.log(jax.random.uniform(ks[3], (H,), minval=0.01, maxval=16.0))
    dt = jnp.exp(jax.random.uniform(
        ks[4], (H,), minval=np.log(1e-3), maxval=np.log(1e-1)))
    g, beta = gd.gate_and_beta(a, b, a_log, dt + jnp.log(-jnp.expm1(-dt)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (batch, H, DK, DV))


def test_gate_and_beta_ranges():
    *_, g, beta, _ = _draw(0, 2, 50)
    assert float(g.max()) <= 0.0 and 0.0 < float(jnp.exp(g).min())
    assert 0.0 < float(beta.min()) and float(beta.max()) < 2.0
    assert float(beta.max()) > 1.0  # the factor 2: negative eigenvalues


@pytest.mark.parametrize("length", [1, 5, 63, 64, 130])
@pytest.mark.parametrize("start", ["zero", "random"])
@pytest.mark.parametrize("alike", [False, True], ids=["spread", "alike"])
def test_chunked_equals_recurrence(length, start, alike):
    q, k, v, g, beta, state = _draw(length, 2, length, alike)
    if start == "zero":
        state = jnp.zeros_like(state)
    want_o, want_s = REC(q, k, v, g, beta, state)
    got_o, got_s = CHK(q, k, v, g, beta, state)
    tol = TOL_ALIKE if alike else TOL
    np.testing.assert_allclose(got_o, want_o, atol=tol, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=tol, rtol=0)


@pytest.mark.parametrize("cut", [17, 64])
def test_chunked_resumes_from_its_own_state(cut):
    """Two calls, the second from the first's state, are one call: what a
    chunked prefill and a restored snapshot rest on."""
    q, k, v, g, beta, state = _draw(7, 1, 130)
    want_o, want_s = CHK(q, k, v, g, beta, state)
    o1, s1 = CHK(*(a[:, :cut] for a in (q, k, v, g, beta)), state)
    o2, s2 = CHK(*(a[:, cut:] for a in (q, k, v, g, beta)), s1)
    np.testing.assert_allclose(
        jnp.concatenate([o1, o2], axis=1), want_o, atol=TOL, rtol=0)
    np.testing.assert_allclose(s2, want_s, atol=TOL, rtol=0)


def test_a_padded_token_leaves_the_state_alone():
    """g = 0, beta = 0: what the prefill program gives the tokens past a
    bucket's length."""
    q, k, v, g, beta, state = _draw(3, 1, 40)
    live = (jnp.arange(40) < 23)[None, :, None]
    _, padded = CHK(q, k, v, jnp.where(live, g, 0.0),
                    jnp.where(live, beta, 0.0), state)
    _, cut = REC(*(a[:, :23] for a in (q, k, v, g, beta)), state)
    np.testing.assert_allclose(padded, cut, atol=TOL, rtol=0)


@pytest.mark.parametrize("batch,n_live", [(1, 1), (4, 2), (4, 4), (6, 0)])
@pytest.mark.parametrize("start", ["zero", "random"])
def test_state_step_equals_recurrence_on_the_live_rows(batch, n_live, start):
    q, k, v, g, beta, state = _draw(batch + n_live, batch, 1)
    if start == "zero":
        state = jnp.zeros_like(state)
    rows = jnp.asarray(
        np.random.default_rng(batch).permutation(batch), jnp.int32)
    pool = jnp.full((2, batch + 1, DK, H * DV), 7.0).at[1, :batch].set(
        gd.to_pool_layout(state))
    want_o, want_s = REC(q, k, v, g, beta, state)
    got_o, got_pool = jax.jit(gd.state_step)(
        pool, 1, rows, n_live, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    got_s = gd.from_pool_layout(got_pool[1, :batch], H)
    live, dead = np.asarray(rows[:n_live]), np.asarray(rows[n_live:])
    np.testing.assert_allclose(got_o[live], want_o[live, 0], atol=TOL, rtol=0)
    np.testing.assert_allclose(got_s[live], want_s[live], atol=TOL, rtol=0)
    # a row that is not live keeps its state bit for bit and yields zeros;
    # the other layer and the row past the batch are not touched
    np.testing.assert_array_equal(got_s[dead], state[dead])
    np.testing.assert_array_equal(got_o[dead], 0.0)
    np.testing.assert_array_equal(got_pool[0], pool[0])
    np.testing.assert_array_equal(got_pool[1, batch], pool[1, batch])


def test_pool_layout_round_trip():
    state = jax.random.normal(jax.random.PRNGKey(0), (2, 5, H, DK, DV))
    rows = gd.to_pool_layout(state)
    assert rows.shape == (2, 5, DK, H * DV)
    np.testing.assert_array_equal(gd.from_pool_layout(rows, H), state)
    # lane h*dv + v of row k holds S_h[k, v]
    assert float(rows[1, 2, 3, 1 * DV + 5]) == float(state[1, 2, 1, 3, 5])


def _conv_by_hand(u, w, tail):
    window = np.concatenate([tail, u], axis=1)
    out = np.zeros_like(u)
    for t in range(u.shape[1]):
        acc = sum(w[:, j] * window[:, t + j] for j in range(w.shape[1]))
        out[:, t] = acc / (1.0 + np.exp(-acc))
    return out


@pytest.mark.parametrize("cut", [1, 2, 3, 10, 64])
def test_conv_across_a_boundary_and_from_a_restored_tail(cut):
    rng = np.random.default_rng(cut)
    u = rng.normal(size=(2, 70, 12)).astype(np.float32)
    w = rng.normal(size=(12, 4)).astype(np.float32)
    zeros = np.zeros((2, 3, 12), np.float32)
    whole = gd.causal_conv(u, w, zeros)
    np.testing.assert_allclose(whole, _conv_by_hand(u, w, zeros), atol=1e-5)
    # the first `cut` tokens, then the rest from the tail they leave
    tail = gd.conv_tail(u[:, :cut], zeros, cut)
    np.testing.assert_array_equal(
        tail, np.concatenate([zeros, u[:, :cut]], axis=1)[:, -3:])
    rest = gd.causal_conv(u[:, cut:], w, tail)
    np.testing.assert_allclose(rest, whole[:, cut:], atol=1e-6)
    # a bucket's padding past `cut` does not reach the tail
    padded = np.concatenate([u[:, :cut], 9 * np.ones((2, 5, 12), np.float32)], 1)
    np.testing.assert_array_equal(gd.conv_tail(padded, zeros, cut), tail)
