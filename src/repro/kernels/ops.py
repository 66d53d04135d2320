"""Public jit'd wrappers around the Pallas kernels.

Dispatch policy:
* on TPU — compiled Pallas (Mosaic),
* elsewhere (this container: CPU) — Pallas ``interpret=True`` when
  ``REPRO_PALLAS_INTERPRET=1`` (used by the kernel test suite), otherwise the
  pure-jnp reference (fast path for the federated simulation, identical
  semantics — asserted by tests/test_kernels.py).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bilinear_score import bilinear_cand_score_pallas
from repro.kernels.change_score import change_score_pallas
from repro.kernels.kge_score import (
    MXU_MODES,
    dist_cand_score_pallas,
    rotate_neg_score_pallas,
    transe_neg_score_pallas,
)
from repro.kernels.sparse_apply import sparse_apply_pallas
from repro.kge import scoring


def _mode() -> str:
    if jax.default_backend() == "tpu":
        return "tpu"
    if os.environ.get("REPRO_PALLAS_INTERPRET", "0") == "1":
        return "interpret"
    return "ref"


def change_score(current: jnp.ndarray, history: jnp.ndarray) -> jnp.ndarray:
    """(N, D) x (N, D) -> (N,) fused 1-cosine change scores (Eq. 1)."""
    mode = _mode()
    if mode == "ref":
        return ref.change_score_ref(current, history)
    return change_score_pallas(current, history, interpret=(mode == "interpret"))


def transe_neg_score(h, r, t_neg, gamma: float) -> jnp.ndarray:
    """(B,D),(B,D),(B,N,D) -> (B,N) TransE negative scores."""
    mode = _mode()
    if mode == "ref":
        return ref.transe_neg_score_ref(h, r, t_neg, gamma)
    return transe_neg_score_pallas(h, r, t_neg, gamma, interpret=(mode == "interpret"))


def rotate_neg_score(h, phase, t_neg, gamma: float) -> jnp.ndarray:
    """(B,D),(B,D/2),(B,N,D) -> (B,N) RotatE negative scores."""
    mode = _mode()
    if mode == "ref":
        return ref.rotate_neg_score_ref(h, phase, t_neg, gamma)
    return rotate_neg_score_pallas(h, phase, t_neg, gamma, interpret=(mode == "interpret"))


def kge_score_rows(h, r, t, method: str, gamma: float) -> jnp.ndarray:
    """Score already-gathered embedding rows (broadcasting, jnp semantics).

    Always the exact :mod:`repro.kge.scoring` arithmetic — this is the gold
    path of the batched evaluator, whose rank-exactness contract with the
    numpy oracle depends on candidate and gold scores sharing one
    definition.
    """
    return scoring.get_score_fn(method)(h, r, t, gamma)


def kge_cand_scores(h, r, t, cand, method: str, gamma: float):
    """Both filtered-ranking legs against a shared candidate block.

    ``h``/``r``/``t``: ``(..., B, D[r])`` gathered query rows;
    ``cand``: ``(..., N, D)`` candidate entity rows shared across the batch
    (leading axes, e.g. the client axis, broadcast/vmap through).  Returns
    ``(tail_scores, head_scores)``, each ``(..., B, N)``.

    Dispatch is by the registry's family tag
    (:attr:`repro.kge.scoring.ScoringSpec.family`): on TPU/interpret the
    distance family runs through the tiled ``dist_cand_score_pallas`` eval
    kernel and the bilinear family (ComplEx/DistMult — contractions, not
    distances) through the matmul-style ``bilinear_cand_score_pallas``,
    both with per-leg query rows precomputed by ``spec.cand_queries`` (see
    the kernel docstrings for the algebra).  The ref path broadcasts the
    exact :mod:`repro.kge.scoring` functions, which is what the
    oracle-exactness property tests pin.  Unknown methods raise the
    registry's ValueError listing every registered name.
    """
    spec = scoring.get_scoring(method)
    mode = _mode()
    if mode == "ref":
        ts = spec.score(
            h[..., :, None, :], r[..., :, None, :], cand[..., None, :, :], gamma
        )
        hs = spec.score(
            cand[..., None, :, :], r[..., :, None, :], t[..., :, None, :], gamma
        )
        return ts, hs
    interpret = mode == "interpret"
    q_t, q_h = spec.cand_queries(h, r, t, gamma)
    hs = None
    if not spec.fold_head:
        # head leg nonlinear in the candidate (spec.cand_queries gave no
        # q_head): evaluate score(c, r, t) exactly on the RAW candidate
        # block, before cand_prep rewrites it for the kernel.
        hs = spec.score(
            cand[..., None, :, :], r[..., :, None, :], t[..., :, None, :], gamma
        )
    cand = spec.cand_prep(cand, gamma)
    if spec.family == "distance":
        statics = spec.kernel_statics(gamma, h.shape[-1])
        fn = lambda q, c: dist_cand_score_pallas(  # noqa: E731
            q, c, gamma, method=spec.kernel_mode, interpret=interpret,
            **statics
        )
    else:  # bilinear: both legs are q @ cand^T on the MXU
        fn = lambda q, c: bilinear_cand_score_pallas(  # noqa: E731
            q, c, interpret=interpret
        )
    for _ in range(h.ndim - 2):  # leading client axes
        fn = jax.vmap(fn)
    return fn(q_t, cand), (hs if hs is not None else fn(q_h, cand))


def cand_legs_on_mxu(method: str) -> int:
    """How many of the two legs :func:`kge_cand_scores` reduces on the MXU
    under this process's dispatch: both for TransE and the bilinear family
    (the tail leg alone where the head leg does not fold), none for the
    other distance methods or on the ref path."""
    spec = scoring.get_scoring(method)
    if _mode() == "ref" or (spec.family == "distance"
                            and spec.kernel_mode not in MXU_MODES):
        return 0
    return 2 if spec.fold_head else 1


def sparse_apply(emb, agg, priority, sign) -> jnp.ndarray:
    """Masked Eq. 4 row update."""
    mode = _mode()
    if mode == "ref":
        return ref.sparse_apply_ref(emb, agg, priority, sign)
    return sparse_apply_pallas(emb, agg, priority, sign, interpret=(mode == "interpret"))


def ssd_chunk(x, b, c, dt, ld, h_prev):
    """One Mamba2 SSD chunk: (y (B,L,H,P), h_new (B,H,N,P))."""
    mode = _mode()
    if mode == "ref":
        return ref.ssd_chunk_ref(x, b, c, dt, ld, h_prev)
    from repro.kernels.ssd_chunk import ssd_chunk_pallas

    return ssd_chunk_pallas(x, b, c, dt, ld, h_prev,
                            interpret=(mode == "interpret"))
