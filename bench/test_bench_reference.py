"""The plain reference's RotatE, its blocking rules and the RotatE costs; the
bilinear eval kernel's roofline reader on a trace built by hand.  Nothing
here imports the program: how the program parametrises a method is its own
tests' business."""
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import costs
import reference
import tracereduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
GAMMA = 9.0  # the published FB15k-237 RotatE setting


def tables(method, dim, entities=40, relations=6, seed=2**31 + 5, cid=1):
    return reference.init_tables(seed, cid, entities, relations, dim, GAMMA,
                                 jnp.float32, method)


def complex_rows(x):
    """``(n, D)`` real rows as ``(n, D/2)`` complex ones: real half first."""
    x = np.asarray(x, np.float64)
    d = x.shape[-1] // 2
    return x[..., :d] + 1j * x[..., d:]


def test_rotate_score_is_the_programs_on_the_phase():
    """The reference's RotatE is the published formula, written out here in
    complex arithmetic: gamma - sum_j |h_j exp(i phase_j) - t_j|, with the
    relation read as the phase r / (range / pi)."""
    dim = 64
    p = tables("rotate", dim)
    rng = (GAMMA + 2) / (dim // 2)
    ent, rel = p["entity"], p["relation"]
    h, t = ent[:24], ent[16:40]
    r = jnp.tile(rel, (4, 1))
    rot = np.exp(1j * np.asarray(r, np.float64) / (rng / math.pi))
    want = GAMMA - np.sum(np.abs(complex_rows(h) * rot - complex_rows(t)), axis=-1)
    # float32 rounding of scores of order gamma
    np.testing.assert_allclose(reference.rotate(h, r, t, GAMMA), want, rtol=0,
                               atol=1e-5)
    # and it is a distance: a tail equal to the rotated head scores gamma
    hr = complex_rows(h) * rot
    tail = jnp.asarray(np.concatenate([hr.real, hr.imag], axis=-1), jnp.float32)
    np.testing.assert_allclose(reference.rotate(h, r, tail, GAMMA), GAMMA,
                               rtol=0, atol=1e-5)


def test_rotate_tables_at_the_published_widths():
    dim, seed, cid = 2000, 2**31 + 9, 2
    m = reference.METHODS["rotate"]
    rng = (GAMMA + 2) / (dim / 2)
    assert m.rel_width(dim) == 1000 and m.init_range(GAMMA, dim) == rng
    p = tables("rotate", dim, entities=300, relations=20, seed=seed, cid=cid)
    assert p["entity"].shape == (300, 2000) and p["relation"].shape == (20, 1000)
    for leaf in p.values():
        top = float(jnp.max(jnp.abs(leaf)))
        assert 0.99 * rng < top <= rng
    # read as a phase, the relation table covers the whole circle, +-pi
    # (float64 rounding of r * pi / range at r = range)
    phase = np.abs(np.asarray(p["relation"], np.float64)) * math.pi / rng
    assert 0.99 * math.pi < phase.max() <= math.pi * (1 + 1e-12)


def test_block_rules_at_the_cells_sizes():
    # today's cells, D=256, B=512, 64 negatives, E_max 14,537: unblocked
    assert reference.neg_block(512, 64, 256) == 64
    assert reference.rank_block(14537, 256) == 32
    # RotatE at its published widths: 2.1 GB a side, blocks of 64
    # negatives (both sides 1 GiB); 9 rows ranked at once
    assert reference.neg_block(1024, 256, 2000) == 64
    assert reference.rank_block(14537, 2000) == 9
    assert reference.neg_block(4, 7, 8, budget=1) == 1
    assert reference.rank_block(10**9, 2000) == 1
    # the filter's pairs: the largest leg of today's cells, R3's second
    # client's whole test split (632,235 pairs, 647 MB a side at D=256), is
    # one call; at D=2000 it is 5.1 GB a side, blocks of 67,108 pairs
    assert reference.pair_block(632235, 256) == 632235
    assert reference.pair_block(632235, 2000) == 67108
    assert reference.pair_block(0, 2000) == 1
    assert reference.pair_block(10, 2000, budget=1) == 1


def _steps(method, budget, half_batch, steps=3, b=8, n=6, dim=16):
    p = tables(method, dim)
    zeros = {k: jnp.zeros_like(v) for k, v in p.items()}
    key = jax.random.PRNGKey(3)
    pos = jnp.stack([
        jax.random.randint(jax.random.fold_in(key, 1), (steps, b), 0, 40),
        jax.random.randint(jax.random.fold_in(key, 2), (steps, b), 0, 6),
        jax.random.randint(jax.random.fold_in(key, 3), (steps, b), 0, 40),
    ], axis=-1)
    nt = jax.random.randint(jax.random.fold_in(key, 4), (steps, b, n), 0, 40)
    nh = jax.random.randint(jax.random.fold_in(key, 5), (steps, b, n), 0, 40)
    # lr 0.1 so that three steps move every touched row well past rounding
    return reference.train_client(p, zeros, zeros, jnp.int32(0), pos, nt, nh,
                                  method=method, gamma=GAMMA, lr=0.1, temp=1.0,
                                  half_batch=half_batch, budget=budget)


@pytest.mark.parametrize("half_batch", [False, True])
@pytest.mark.parametrize("method", ["transe", "complex", "rotate"])
def test_blocked_step_is_the_unblocked_one(method, half_batch):
    # one side's (8, 6, 16) float32 block is 3,072 bytes; a budget of
    # 2,048 holds both sides' blocks of 2 negatives, so 3 blocks
    assert reference.neg_block(8, 6, 16, budget=2048) == 2
    whole = _steps(method, reference.BUDGET, half_batch)
    blocked = _steps(method, 2048, half_batch)
    # the same sums in another order: float32 rounding, measured against
    # each leaf's largest value
    for a, b in zip(jax.tree.leaves(whole[:3]), jax.tree.leaves(blocked[:3])):
        scale = float(jnp.max(jnp.abs(a)))
        np.testing.assert_allclose(b, a, rtol=0, atol=1e-6 * scale)
    assert int(blocked[3]) == 3
    np.testing.assert_allclose(blocked[4], whole[4], rtol=1e-6)
    moved = jnp.abs(blocked[0]["entity"] - tables(method, 16)["entity"])
    assert float(jnp.max(moved)) > 1e-3


@pytest.mark.parametrize("method", ["transe", "complex", "rotate"])
def test_blocked_ranking_gives_the_same_ranks(method):
    p = tables(method, 16)
    rng = np.random.default_rng(4)
    known = np.stack([rng.integers(0, 40, 300), rng.integers(0, 6, 300),
                      rng.integers(0, 40, 300)], axis=1)
    split = known[:37]
    whole = reference.filtered_ranks(p, split, known, method, GAMMA)
    # 40 x 16 float32 rows are 2,560 bytes: a budget of 7,680 ranks 3 rows
    # at once, and 37 rows pad to 13 blocks
    assert reference.rank_block(40, 16, 7680) == 3
    blocked = reference.filtered_ranks(p, split, known, method, GAMMA, budget=7680)
    np.testing.assert_array_equal(blocked, whole)
    assert whole.min() >= 1 and whole.max() > 1


@pytest.mark.parametrize("method", ["transe", "complex", "rotate"])
def test_blocked_filter_gives_the_same_ranks(method, monkeypatch):
    p = tables(method, 16)
    rng = np.random.default_rng(4)
    known = np.stack([rng.integers(0, 40, 300), rng.integers(0, 6, 300),
                      rng.integers(0, 40, 300)], axis=1)
    split = known[:37]
    calls = []
    pair_scores = reference._pair_scores

    def counted(ent, rel, h, r, t, **kw):
        calls.append(h.shape[0])
        return pair_scores(ent, rel, h, r, t, **kw)

    monkeypatch.setattr(reference, "_pair_scores", counted)
    whole = reference.filtered_ranks(p, split, known, method, GAMMA)
    legs = list(calls)
    # 16 float32 coordinates are 64 bytes a side: a budget of 448 holds one
    # side of 7 pairs, so a leg of more is scored 3 pairs (384 bytes) at a
    # time
    assert reference.pair_block(8, 16, 448) == 3
    calls.clear()
    blocked = reference.filtered_ranks(p, split, known, method, GAMMA, budget=448)
    assert len(legs) == 2 and min(legs) > 7
    assert max(calls) == 3 and sum(calls) == sum(legs)
    assert len(calls) == sum(-(-n // 3) for n in legs)
    np.testing.assert_array_equal(blocked, whole)
    assert whole.min() >= 1 and whole.max() > 1


def test_rotate_costs_by_hand():
    # per complex coordinate: rotation 6, subtract 2, squares and add 3,
    # root 1, sum 1 = 13; a folded-query candidate 7; d = 2000 is 1,000
    assert costs.SCORE_FLOPS["rotate"](2000) == 13 * 1000
    assert costs.CAND_FLOPS["rotate"](2000) == 7 * 1000
    assert costs.train_flops("rotate", 2000, 10, 256) == 3 * 10 * 513 * 13000
    assert costs.eval_flops("rotate", 2000, 5) == 2 * 5 * 7000
    f, b = costs.cand_score_cost("rotate", 8, 128, 2000)
    assert (f, b) == (8 * 128 * 7000, 4 * (8 * 2000 + 128 * 2000 + 8 * 128))


def metric(name):
    spec = importlib.util.spec_from_file_location(
        "reference_metric_" + name, os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def bilinear_trace():
    """One TPU plane; ns times; window 0..1000.  Two calls of the bilinear
    kernel as the vmapped eval names them (tail leg 100..300, head leg
    300..500), a fusion between them and a dist kernel op, which the
    reader must not count."""
    ops = [
        ("vmap_jit_bilinear_cand_score_pallas__.4", 100, 300, "f32[2,8,128]"),
        ("vmap_jit_bilinear_cand_score_pallas__.5", 300, 500, "f32[2,8,128]"),
        ("fusion.3", 500, 600, "f32[8]"),
        ("dist_cand_score_pallas.1", 600, 900, "f32[8,128]"),
    ]
    return tr.Trace(devices={"/device:TPU:0": ops},
                    host=[("window", 0, 1000, "window")])


def test_bilinear_cand_roofline_reader():
    """Two clients of 3 and 5 real queries against 10 and 20 entities, D=16,
    two calls in the window: each call scores both legs of every client."""
    red = tr.reduce(bilinear_trace(), "window")
    read = metric("bilinear_cand_roofline")
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = {"trace": red, "peak": peak, "cfg": {"method": "complex", "dim": 16},
           "shapes": {"queries": [3, 5], "entities": [10, 20]},
           "work": {"calls": 2}}
    flops = 2 * 2 * (3 * 10 * 32 + 5 * 20 * 32)
    nbytes = 2 * 2 * 4 * ((3 * 16 + 10 * 16 + 3 * 10) + (5 * 16 + 20 * 16 + 5 * 20))
    # bytes bound: 8,384 bytes at 1e9 B/s against 16,640 FLOP at 1e12
    assert nbytes / 1e9 > flops / 1e12
    assert read(ctx) == pytest.approx(100 * nbytes / 1e9 / 400e-9)
    # the dist reader reads its own kernel alone
    assert metric("dist_cand_roofline")(ctx) == pytest.approx(
        100 * nbytes / 1e9 / 300e-9)
    # no bilinear op in the window: no reading, not 0
    red.ops[0][:] = [o for o in red.ops[0] if "bilinear" not in o[0]]
    assert read(ctx) is None
