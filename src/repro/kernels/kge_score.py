"""KGE triple-scoring Pallas kernels (TransE / RotatE negative scoring).

The client-side compute hot spot of FedE-style training is scoring a batch of
positive triples against N negatives: for TransE that is
``gamma - ||h + r - t_neg||`` over a (B, N, D) tensor.  XLA materialises the
(B, N, D) difference tensor in HBM; we instead tile (batch-block x neg-block)
so the difference lives only in VMEM/VREGs.

Tiling:
* grid (B/BB, N/BN); per step the kernel sees h,r blocks (BB, D) and a
  negatives block (BB, BN, D), writes scores (BB, BN),
* D padded to a lane multiple with zeros (exact for the distance: zero-padded
  coordinates contribute 0 to h + r - t when all three are padded),
* BB, BN chosen by the wrapper so the negative block fits VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.vma import out_struct


def _transe_kernel(gamma, h_ref, r_ref, t_ref, out_ref):
    h = h_ref[...].astype(jnp.float32)  # (BB, D)
    r = r_ref[...].astype(jnp.float32)  # (BB, D)
    t = t_ref[...].astype(jnp.float32)  # (BB, BN, D)
    d = (h + r)[:, None, :] - t
    dist = jnp.sqrt(jnp.maximum(jnp.sum(d * d, axis=-1), 1e-24))
    out_ref[...] = gamma - dist


def _rotate_kernel(gamma, half, h_ref, p_ref, t_ref, out_ref):
    h = h_ref[...].astype(jnp.float32)  # (BB, D)
    phase = p_ref[...].astype(jnp.float32)  # (BB, half_padded)
    t = t_ref[...].astype(jnp.float32)  # (BB, BN, D)
    h_re, h_im = h[:, :half], h[:, half : 2 * half]
    t_re, t_im = t[:, :, :half], t[:, :, half : 2 * half]
    ph = phase[:, :half]
    r_re, r_im = jnp.cos(ph), jnp.sin(ph)
    d_re = (h_re * r_re - h_im * r_im)[:, None, :] - t_re
    d_im = (h_re * r_im + h_im * r_re)[:, None, :] - t_im
    dist = jnp.sqrt(d_re * d_re + d_im * d_im + 1e-12).sum(axis=-1)
    out_ref[...] = gamma - dist


@functools.partial(
    jax.jit, static_argnames=("gamma", "block_b", "block_n", "interpret")
)
def transe_neg_score_pallas(
    h: jnp.ndarray,  # (B, D)
    r: jnp.ndarray,  # (B, D)
    t_neg: jnp.ndarray,  # (B, N, D)
    gamma: float,
    block_b: int = 8,
    block_n: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, n, d = t_neg.shape
    d_pad = (-d) % 128
    b_pad = (-b) % block_b
    n_pad = (-n) % block_n
    h = jnp.pad(h, ((0, b_pad), (0, d_pad)))
    r = jnp.pad(r, ((0, b_pad), (0, d_pad)))
    t_neg = jnp.pad(t_neg, ((0, b_pad), (0, n_pad), (0, d_pad)))
    bf, nf, df = t_neg.shape

    out = pl.pallas_call(
        functools.partial(_transe_kernel, gamma),
        grid=(bf // block_b, nf // block_n),
        in_specs=[
            pl.BlockSpec((block_b, df), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, df), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, block_n, df), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        out_shape=out_struct((bf, nf), jnp.float32, h, r, t_neg),
        interpret=interpret,
    )(h, r, t_neg)
    return out[:b, :n]


def _dist_cand_kernel(gamma, mode, half, modulus, q_ref, c_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)  # (BB, D)
    c = c_ref[...].astype(jnp.float32)  # (BN, D)
    d = q[:, None, :] - c[None, :, :]  # (BB, BN, D)
    if mode == "rotate":  # unit-modulus rotation folded into q
        d_re, d_im = d[:, :, :half], d[:, :, half : 2 * half]
        dist = jnp.sqrt(d_re * d_re + d_im * d_im + 1e-12).sum(axis=-1)
    else:  # protate: q AND c in phase units, weighted |sin| distance
        dist = jnp.abs(jnp.sin(d)).sum(axis=-1) * modulus
    out_ref[...] = gamma - dist


# distance modes whose eval kernel reduces on the MXU
MXU_MODES = ("transe",)
# the MXU form's squared distance is off by some ulp of ||q||^2 + ||c||^2:
# a pair under this share of that sum is scored again from the difference,
# so the others keep a distance within about 8 * (that many ulp) relative
_CLOSE = 2.0 ** -4
_SUB_B, _SUB_N = 8, 128  # sub-block of that exact pass, as the VPU tiles
_VMEM_BUDGET = 12 * 2**20


def _dot_t(a, b):
    """``a @ b^T`` in f32 on the MXU."""
    return jax.lax.dot_general(
        a, b,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def _transe_dist(q, c):
    """``||q_b - c_n||`` -> (BB, BN) from the ``(BB, BN, D)`` difference."""
    d = q[:, None, :] - c[None, :, :]
    return jnp.sqrt(jnp.maximum(jnp.sum(d * d, axis=-1), 1e-24))


def _transe_mxu_kernel(gamma, sub_b, sub_n, q_ref, c_ref, out_ref):
    """TransE tile as ``||q||^2 + ||c||^2 - 2 q.c`` with the cross term on
    the MXU; sub-blocks holding a pair too close for that form are scored
    again from the difference."""
    q = q_ref[...].astype(jnp.float32)  # (BB, D)
    c = c_ref[...].astype(jnp.float32)  # (BN, D)
    norms = (jnp.sum(q * q, axis=1, keepdims=True)  # (BB, 1)
             # c's squared norms along lanes, (1, BN)
             + _dot_t(jnp.ones((8, q.shape[1]), jnp.float32), c * c)[:1])
    d2 = norms - 2.0 * _dot_t(q, c)
    # NaN marks the pairs to score again (and a NaN input, which gives NaN
    # either way)
    ok = d2 >= _CLOSE * norms
    out_ref[...] = jnp.where(ok, gamma - jnp.sqrt(jnp.maximum(d2, 1e-24)),
                             jnp.nan)

    @pl.when(jnp.max(jnp.where(ok, 0.0, 1.0)) > 0.0)
    def _():
        def rows(i, carry):
            r = pl.ds(pl.multiple_of(i * sub_b, sub_b), sub_b)
            for j in range(0, out_ref.shape[1], sub_n):
                n = slice(j, j + sub_n)
                blk = out_ref[r, n]

                @pl.when(jnp.max(jnp.where(blk == blk, 0.0, 1.0)) > 0.0)
                def _():
                    out_ref[r, n] = gamma - _transe_dist(
                        q_ref[r, :].astype(jnp.float32),
                        c_ref[n, :].astype(jnp.float32),
                    )
            return carry

        jax.lax.fori_loop(0, out_ref.shape[0] // sub_b, rows, 0)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _mxu_tiles(b: int, n: int, df: int) -> tuple[int, int]:
    """MXU-form tiles: the whole candidate chunk (to 512 columns) against
    up to 256 query rows, fewer where the blocks and the tile's f32
    intermediates would pass ``_VMEM_BUDGET``."""
    bn = min(512, _round_up(n, 128))
    bb = min(256, _round_up(b, 8))
    while bb > 8 and 4 * (2 * (bb + bn) * df + 4 * bb * bn) > _VMEM_BUDGET:
        bb = max(8, bb // 16 * 8)
    return bb, bn


@functools.partial(
    jax.jit,
    static_argnames=("gamma", "method", "modulus", "block_b", "block_n",
                     "interpret"),
)
def dist_cand_score_pallas(
    q: jnp.ndarray,  # (B, D) per-query rows (leg-specific, see kernels.ops)
    cand: jnp.ndarray,  # (N, D) candidate rows SHARED across the batch
    gamma: float,
    method: str = "transe",
    modulus: float = 1.0,
    block_b: int | None = None,
    block_n: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Evaluation-shaped scoring: ``gamma - dist(q_b, cand_n)`` -> (B, N).

    Unlike the training kernels above (per-query ``(B, N, D)`` negatives),
    filtered-ranking eval scores every query against ONE shared candidate
    block, so the kernel tiles (query-block x candidate-block).  Both legs of
    every distance-family model reduce to this form with a precomputed query
    row (:attr:`repro.kge.scoring.ScoringSpec.cand_queries`): TransE tail
    ``q = h + r``, head ``q = t - r``; RotatE tail ``q = h∘r``, head
    ``q = t∘conj(r)`` (unit-modulus rotations preserve the distance);
    pRotatE rescales both q and the candidate block to phase units and takes
    the ``modulus``-weighted ``|sin|`` distance.

    TransE (:data:`MXU_MODES`) scores ``||q||^2 + ||c||^2 - 2 q.c`` with the
    cross term on the MXU, in f32, over tiles of up to 256 queries against
    the whole candidate chunk (:func:`_mxu_tiles`); the few sub-blocks that
    hold a near pair are scored again from the difference.  RotatE and
    pRotatE reduce the ``(BB, BN, D)`` difference per coordinate on the VPU
    over 8 x 128 tiles.  ``block_b`` / ``block_n`` override the tiles.
    D, B and N are zero-padded (exact: padded coordinates add 0 to the
    norms, the dot product and ``q - cand``, and ``sin(0) = 0``; RotatE
    slices its true halves before the modulus); the padding is sliced off.
    """
    b, d = q.shape
    n = cand.shape[0]
    half = d // 2
    df = _round_up(d, 128)
    mxu = method in MXU_MODES
    bb, bn = _mxu_tiles(b, n, df) if mxu else (8, 128)
    bb, bn = block_b or bb, block_n or bn
    if mxu:
        kernel = functools.partial(_transe_mxu_kernel, gamma,
                                   math.gcd(bb, _SUB_B), math.gcd(bn, _SUB_N))
    else:
        kernel = functools.partial(_dist_cand_kernel, gamma, method, half,
                                   modulus)
    q = jnp.pad(q, ((0, (-b) % bb), (0, df - d)))
    cand = jnp.pad(cand, ((0, (-n) % bn), (0, df - d)))
    bf = q.shape[0]
    nf = cand.shape[0]

    out = pl.pallas_call(
        kernel,
        grid=(bf // bb, nf // bn),
        in_specs=[
            pl.BlockSpec((bb, df), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, df), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j: (i, j)),
        out_shape=out_struct((bf, nf), jnp.float32, q, cand),
        interpret=interpret,
    )(q, cand)
    return out[:b, :n]


@functools.partial(
    jax.jit, static_argnames=("gamma", "block_b", "block_n", "interpret")
)
def rotate_neg_score_pallas(
    h: jnp.ndarray,  # (B, D)
    phase: jnp.ndarray,  # (B, D/2)
    t_neg: jnp.ndarray,  # (B, N, D)
    gamma: float,
    block_b: int = 8,
    block_n: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, n, d = t_neg.shape
    half = d // 2
    d_pad = (-d) % 128
    p_pad = (-phase.shape[-1]) % 128
    b_pad = (-b) % block_b
    n_pad = (-n) % block_n
    h = jnp.pad(h, ((0, b_pad), (0, d_pad)))
    phase = jnp.pad(phase, ((0, b_pad), (0, p_pad)))
    t_neg = jnp.pad(t_neg, ((0, b_pad), (0, n_pad), (0, d_pad)))
    bf, nf, df = t_neg.shape

    out = pl.pallas_call(
        functools.partial(_rotate_kernel, gamma, half),
        grid=(bf // block_b, nf // block_n),
        in_specs=[
            pl.BlockSpec((block_b, df), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, phase.shape[-1]), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, block_n, df), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        out_shape=out_struct((bf, nf), jnp.float32, h, phase, t_neg),
        interpret=interpret,
    )(h, phase, t_neg)
    return out[:b, :n]
