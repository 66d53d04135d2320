"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle.

Sweeps shapes (including non-aligned N and D) and dtypes, per the brief.
Also asserts the ops-layer dispatch (ref fallback) is bit-compatible with the
kernels so the federated simulation and the TPU path compute the same thing.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.change_score import change_score_pallas
from repro.kernels.kge_score import rotate_neg_score_pallas, transe_neg_score_pallas
from repro.kernels.sparse_apply import sparse_apply_pallas


SHAPES_ND = [(8, 16), (100, 64), (257, 130), (512, 256), (33, 100)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES_ND)
@pytest.mark.parametrize("dtype", DTYPES)
def test_change_score_kernel(shape, dtype):
    n, d = shape
    k1, k2 = jax.random.split(jax.random.PRNGKey(n * d))
    cur = jax.random.normal(k1, (n, d)).astype(dtype)
    hist = (jax.random.normal(k2, (n, d)) * 0.5 + cur.astype(jnp.float32)).astype(dtype)
    got = change_score_pallas(cur, hist, block_rows=64, interpret=True)
    want = ref.change_score_ref(cur.astype(jnp.float32), hist.astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


@pytest.mark.parametrize("b,n,d", [(4, 8, 32), (7, 33, 64), (16, 128, 100)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_transe_kernel(b, n, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(b * n + d), 3)
    h = jax.random.normal(ks[0], (b, d)).astype(dtype)
    r = jax.random.normal(ks[1], (b, d)).astype(dtype)
    t = jax.random.normal(ks[2], (b, n, d)).astype(dtype)
    got = transe_neg_score_pallas(h, r, t, gamma=8.0, block_b=4, block_n=32, interpret=True)
    want = ref.transe_neg_score_ref(
        h.astype(jnp.float32), r.astype(jnp.float32), t.astype(jnp.float32), 8.0
    )
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


@pytest.mark.parametrize("b,n,d", [(4, 8, 32), (6, 20, 64)])
def test_rotate_kernel(b, n, d):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    h = jax.random.normal(ks[0], (b, d))
    phase = jax.random.uniform(ks[1], (b, d // 2), minval=-3.14, maxval=3.14)
    t = jax.random.normal(ks[2], (b, n, d))
    got = rotate_neg_score_pallas(h, phase, t, gamma=8.0, block_b=2, block_n=8, interpret=True)
    want = ref.rotate_neg_score_ref(h, phase, t, 8.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,n,d", [(4, 8, 32), (7, 150, 64), (16, 257, 100)])
@pytest.mark.parametrize("method", ["transe", "rotate"])
def test_dist_cand_score_kernel(b, n, d, method):
    """The eval-shaped kernel (shared candidate block across the batch) vs
    the exact scoring-fn broadcast the ref dispatch path uses."""
    from repro.kernels.kge_score import dist_cand_score_pallas
    from repro.kge.scoring import get_score_fn

    if method == "rotate" and d % 2:
        d += 1
    ks = jax.random.split(jax.random.PRNGKey(b * n + d), 3)
    cand = jax.random.normal(ks[2], (n, d))
    score = get_score_fn(method)
    if method == "transe":
        h = jax.random.normal(ks[0], (b, d))
        r = jax.random.normal(ks[1], (b, d))
        q = h + r  # tail-leg query rows (see kernels.ops.kge_cand_scores)
        want = score(h[:, None, :], r[:, None, :], cand[None, :, :], 8.0)
    else:
        h = jax.random.normal(ks[0], (b, d))
        phase = jax.random.uniform(ks[1], (b, d // 2), minval=-3.14, maxval=3.14)
        half = d // 2
        h_re, h_im = h[:, :half], h[:, half:]
        q = jnp.concatenate(
            [h_re * jnp.cos(phase) - h_im * jnp.sin(phase),
             h_re * jnp.sin(phase) + h_im * jnp.cos(phase)], axis=-1)
        want = score(h[:, None, :], phase[:, None, :], cand[None, :, :], 8.0)
    got = dist_cand_score_pallas(q, cand, 8.0, method=method, block_b=4,
                                 block_n=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,n,d", [(300, 700, 256), (17, 513, 100)])
def test_dist_cand_score_transe_default_tiles(b, n, d):
    """TransE's MXU form at the tiles the wrapper picks, with B and N that
    are no tile multiple, against a float64 distance."""
    from repro.kernels.kge_score import dist_cand_score_pallas

    rng = np.random.default_rng(b * n + d)
    q = rng.normal(size=(b, d))
    cand = rng.normal(size=(n, d))
    want = 8.0 - np.linalg.norm(q[:, None, :] - cand[None, :, :], axis=-1)
    got = dist_cand_score_pallas(jnp.asarray(q, jnp.float32),
                                 jnp.asarray(cand, jnp.float32), 8.0,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("spread", [1e-2, 0.8])
def test_dist_cand_score_transe_near_pairs_at_large_norms(spread):
    """Query rows of norm 30 and candidates drawn around them: the MXU
    form's ``||q||^2 + ||c||^2 - 2 q.c`` cancels about 1800 down to the
    squared distance.  Spread 1e-2 per coordinate (distances about 0.16)
    is the sub-blocks scored again from the difference; 0.8 (distances
    about 13) stays on the MXU form.  Bound: 1e-4, against float64."""
    from repro.kernels.kge_score import dist_cand_score_pallas

    rng = np.random.default_rng(5)
    b, n, d = 64, 512, 256
    q = rng.normal(size=(b, d))
    q *= 30.0 / np.linalg.norm(q, axis=1, keepdims=True)
    cand = q[rng.integers(0, b, n)] + spread * rng.normal(size=(n, d))
    want = 8.0 - np.linalg.norm(q[:, None, :] - cand[None, :, :], axis=-1)
    got = dist_cand_score_pallas(jnp.asarray(q, jnp.float32),
                                 jnp.asarray(cand, jnp.float32), 8.0,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,n,d", [(4, 8, 32), (7, 150, 64), (16, 257, 100)])
@pytest.mark.parametrize("method", ["distmult", "complex"])
def test_bilinear_cand_score_kernel(b, n, d, method):
    """The bilinear (MXU contraction) eval kernel vs the exact scoring-fn
    broadcast, both legs, using the registry's own query-row folding."""
    from repro.kernels.bilinear_score import bilinear_cand_score_pallas
    from repro.kge.scoring import get_scoring

    if method == "complex" and d % 2:
        d += 1
    spec = get_scoring(method)
    ks = jax.random.split(jax.random.PRNGKey(b * n + d), 4)
    h = jax.random.normal(ks[0], (b, d))
    r = jax.random.normal(ks[1], (b, spec.rel_dim(d)))
    t = jax.random.normal(ks[2], (b, d))
    cand = jax.random.normal(ks[3], (n, d))
    q_t, q_h = spec.cand_queries(h, r, t, 8.0)
    for q, want in (
        (q_t, spec.score(h[:, None, :], r[:, None, :], cand[None, :, :], 8.0)),
        (q_h, spec.score(cand[None, :, :], r[:, None, :], t[:, None, :], 8.0)),
    ):
        got = bilinear_cand_score_pallas(q, cand, block_b=4, block_n=32,
                                         interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_kge_cand_scores_head_leg_algebra():
    """ops.kge_cand_scores' head-leg query folding (t - r for TransE,
    t∘conj(r) for RotatE, t∘r / the conjugated coefficients for the
    bilinear family) must agree with scoring the candidates as heads
    directly, for EVERY registered method."""
    from repro.kernels import ops
    from repro.kge.scoring import registered_methods

    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    b, n, d = 6, 40, 16
    cand = jax.random.normal(ks[3], (n, d))
    for method, spec in registered_methods().items():
        h = jax.random.normal(ks[0], (b, d))
        r = jax.random.normal(ks[1], (b, spec.rel_dim(d)))
        t = jax.random.normal(ks[2], (b, d))
        _, hs = ops.kge_cand_scores(h, r, t, cand, method, 8.0)
        want = spec.score(cand[None, :, :], r[:, None, :], t[:, None, :], 8.0)
        np.testing.assert_allclose(np.asarray(hs), np.asarray(want),
                                   rtol=1e-5, atol=1e-5, err_msg=method)


@pytest.mark.parametrize(
    "method", ["transe", "rotate", "protate", "distmult", "complex", "proje"]
)
def test_kge_cand_scores_interpret_close_to_ref(monkeypatch, method):
    """Family-tagged Pallas dispatch (interpret) of both legs stays within
    fp tolerance of the exact ref path for every registered method — the
    regression pin for the old silent ComplEx ref fallback."""
    from repro.kernels import ops
    from repro.kge.scoring import get_scoring

    spec = get_scoring(method)
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    b, n, d = 5, 33, 32
    h = jax.random.normal(ks[0], (b, d))
    r = jax.random.normal(ks[1], (b, spec.rel_dim(d)))
    t = jax.random.normal(ks[2], (b, d))
    cand = jax.random.normal(ks[3], (n, d))
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    ts_a, hs_a = ops.kge_cand_scores(h, r, t, cand, method, 8.0)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    ts_b, hs_b = ops.kge_cand_scores(h, r, t, cand, method, 8.0)
    np.testing.assert_allclose(np.asarray(ts_a), np.asarray(ts_b),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hs_a), np.asarray(hs_b),
                               rtol=1e-4, atol=1e-4)


def test_kge_cand_scores_unknown_method_lists_registry():
    """Unknown methods must raise the registry's self-describing error, not
    silently fall back to any kernel."""
    from repro.kernels import ops
    from repro.kge.scoring import registered_methods

    x = jnp.zeros((2, 8))
    cand = jnp.zeros((3, 8))
    with pytest.raises(ValueError) as e:
        ops.kge_cand_scores(x, x, x, cand, "no-such-method", 8.0)
    for name in registered_methods():
        assert name in str(e.value)


@pytest.mark.parametrize("shape", [(16, 8), (100, 64), (257, 100)])
def test_sparse_apply_kernel(shape):
    n, d = shape
    ks = jax.random.split(jax.random.PRNGKey(n), 4)
    emb = jax.random.normal(ks[0], (n, d))
    agg = jax.random.normal(ks[1], (n, d))
    pri = jax.random.randint(ks[2], (n,), 0, 5).astype(jnp.float32)
    sign = (jax.random.uniform(ks[3], (n,)) < 0.4).astype(jnp.int8)
    got = sparse_apply_pallas(emb, agg, pri, sign, block_rows=32, interpret=True)
    want = ref.sparse_apply_ref(emb, agg, pri, sign)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(4, 200),
    d=st.integers(4, 150),
    frac=st.floats(0.05, 0.95),
)
def test_sparse_apply_property(n, d, frac):
    """Property: unselected rows pass through untouched; selected rows obey Eq. 4."""
    ks = jax.random.split(jax.random.PRNGKey(n * 1000 + d), 4)
    emb = jax.random.normal(ks[0], (n, d))
    agg = jax.random.normal(ks[1], (n, d))
    pri = jax.random.randint(ks[2], (n,), 1, 7).astype(jnp.float32)
    sign = (jax.random.uniform(ks[3], (n,)) < frac).astype(jnp.int8)
    out = np.asarray(ref.sparse_apply_ref(emb, agg, pri, sign))
    emb_n, agg_n, pri_n, sign_n = map(np.asarray, (emb, agg, pri, sign))
    unsel = sign_n == 0
    np.testing.assert_array_equal(out[unsel], emb_n[unsel])
    sel = ~unsel
    expect = (agg_n[sel] + emb_n[sel]) / (1.0 + pri_n[sel])[:, None]
    np.testing.assert_allclose(out[sel], expect, rtol=1e-6)


def test_ops_dispatch_ref_equals_interpret(monkeypatch):
    """ops.change_score must give the same numbers in ref and interpret modes."""
    from repro.kernels import ops

    cur = jax.random.normal(jax.random.PRNGKey(0), (60, 48))
    hist = jax.random.normal(jax.random.PRNGKey(1), (60, 48))
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    a = np.asarray(ops.change_score(cur, hist))
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    b = np.asarray(ops.change_score(cur, hist))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("b,l,h,n,p", [(2, 8, 3, 4, 8), (1, 16, 2, 8, 16),
                                        (2, 12, 4, 16, 32)])
def test_ssd_chunk_kernel(b, l, h, n, p):
    from repro.kernels.ssd_chunk import ssd_chunk_pallas

    ks = jax.random.split(jax.random.PRNGKey(b * l + h), 6)
    x = jax.random.normal(ks[0], (b, l, h, p)) * 0.4
    bb = jax.random.normal(ks[1], (b, l, n)) * 0.4
    cc = jax.random.normal(ks[2], (b, l, n)) * 0.4
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, l, h)))
    ld = -jnp.abs(jax.random.normal(ks[4], (b, l, h))) * 0.3
    hp = jax.random.normal(ks[5], (b, h, n, p)) * 0.2
    y0, h0 = ref.ssd_chunk_ref(x, bb, cc, dt, ld, hp)
    y1, h1 = ssd_chunk_pallas(x, bb, cc, dt, ld, hp, interpret=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(h0), np.asarray(h1), rtol=2e-5, atol=2e-5)


def test_ssd_chunk_sequential_equivalence():
    """Two chained chunks == one double-length chunk (state passing)."""
    b, l, h, n, p = 1, 6, 2, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, 2 * l, h, p)) * 0.4
    bb = jax.random.normal(ks[1], (b, 2 * l, n)) * 0.4
    cc = jax.random.normal(ks[2], (b, 2 * l, n)) * 0.4
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, 2 * l, h)))
    ld = -jnp.abs(jax.random.normal(ks[4], (b, 2 * l, h))) * 0.3
    h0 = jnp.zeros((b, h, n, p))
    y_full, h_full = ref.ssd_chunk_ref(x, bb, cc, dt, ld, h0)
    y1, h1 = ref.ssd_chunk_ref(x[:, :l], bb[:, :l], cc[:, :l], dt[:, :l], ld[:, :l], h0)
    y2, h2 = ref.ssd_chunk_ref(x[:, l:], bb[:, l:], cc[:, l:], dt[:, l:], ld[:, l:], h1)
    np.testing.assert_allclose(np.asarray(y_full[:, :l]), np.asarray(y1), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_full[:, l:]), np.asarray(y2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_full), np.asarray(h2), rtol=1e-5, atol=1e-5)
