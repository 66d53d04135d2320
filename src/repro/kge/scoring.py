"""KGE scoring registry: TransE, RotatE, pRotatE, DistMult, ComplEx, HolE.

Conventions (matching FedE / the RotatE reference implementation):

* entity embeddings ``E  : (num_entities, dim)``
* relation embeddings ``R : (num_relations, rel_dim)`` where ``rel_dim`` is a
  per-method rule (:attr:`ScoringSpec.rel_dim`): RotatE stores one phase per
  complex coordinate (``dim/2``); everything else uses ``dim``.
* Complex-valued methods (RotatE, ComplEx) store points of C^{dim/2} as
  (re, im) halves of the real ``dim`` vector.
* Scores are "higher is better".  The **distance** family produces
  ``gamma - distance`` and trains with self-adversarial negative weighting
  (RotatE Eq. 5); the **bilinear** family produces a trilinear contraction
  and trains with uniform negative weighting (FedE convention for ComplEx).

The registry (modeled on :mod:`repro.core.codecs.registry`) is the single
source of truth for which methods exist: construction (:func:`get_scoring`),
the ``--method`` CLI surface (:func:`parse_method`), the engines' loss/score
pieces, the eval-kernel family dispatch in :mod:`repro.kernels.ops`, and
every error message (:func:`scoring_usage`) all derive from it, so adding a
method is one :func:`register` call away from running through all four
engines, the batched evaluator, and the benchmark sweep.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict

import jax
import jax.numpy as jnp

# Method names are plain strings validated by the registry (get_scoring);
# the alias survives for annotations from the pre-registry Literal era.
Method = str

# Initialisation hyper-parameters from the paper (Section IV-B):
# gamma = 8, epsilon = 2; embedding range = (gamma + eps) / dim.
DEFAULT_GAMMA = 8.0
DEFAULT_EPSILON = 2.0

FAMILIES = ("distance", "bilinear")


def _identity_cand_prep(cand: jnp.ndarray, gamma: float) -> jnp.ndarray:
    del gamma
    return cand


def _no_kernel_statics(gamma: float, dim: int) -> dict:
    del gamma, dim
    return {}


@dataclasses.dataclass(frozen=True)
class ScoringSpec:
    """Everything the engines need to know about one scoring method.

    The jit-safe pieces (``score``, ``cand_queries``, ``cand_prep``) close
    over nothing and take only arrays + the static ``gamma``, so they can be
    traced inside any engine program.  ``family`` drives both the loss
    (distance -> self-adversarial weighting, bilinear -> uniform) and the
    eval-kernel dispatch in :func:`repro.kernels.ops.kge_cand_scores`
    (distance -> ``dist_cand_score_pallas``, bilinear -> the matmul-style
    ``bilinear_cand_score_pallas``).
    """

    name: str
    family: str  # "distance" | "bilinear"
    doc: str  # one-line score formula, shown by scoring_usage()
    # (h, r, t, gamma) -> scores; broadcasts over leading/negative axes.
    score: Callable[..., jnp.ndarray]
    rel_dim: Callable[[int], int]  # entity dim -> relation dim
    rel_dim_doc: str  # human-readable rel_dim rule ("dim", "dim/2")
    rel_init: str  # "uniform" (+-embedding_range) | "phase" (+-pi)
    # (h, r, t, gamma) -> (q_tail, q_head): per-leg query rows that reduce
    # BOTH filtered-ranking legs to a (B, D)-vs-candidate-block kernel call
    # (distance: dist(q, cand); bilinear: q @ cand^T).
    cand_queries: Callable[..., tuple]
    # self-adversarial negative weighting in the loss (RotatE Eq. 5)?
    adversarial: bool
    # candidate-block transform applied once per kernel call (pRotatE
    # rescales entity rows to phase units); identity for everything else.
    cand_prep: Callable[..., jnp.ndarray] = _identity_cand_prep
    # False: the head leg score(c, r, t) is NOT linear/foldable in the
    # candidate (ProjE's tanh(c + r)), so cand_queries returns q_head=None
    # and kge_cand_scores evaluates that leg by broadcasting ``score``
    # exactly on every path; the tail leg still rides the eval kernel.
    fold_head: bool = True
    # distance family only: which distance dist_cand_score_pallas computes.
    kernel_mode: str | None = None
    # extra static kwargs for the distance kernel, from (gamma, true dim).
    kernel_statics: Callable[[float, int], dict] = _no_kernel_statics
    aliases: tuple = ()

    def __post_init__(self):
        if self.family not in FAMILIES:
            raise ValueError(
                f"scoring family {self.family!r} not in {FAMILIES}"
            )
        if self.family == "distance" and self.kernel_mode is None:
            raise ValueError(
                f"distance-family method {self.name!r} needs a kernel_mode"
            )


# --------------------------------------------------------------- registry
_REGISTRY: Dict[str, ScoringSpec] = {}
_ALIASES: Dict[str, str] = {}


def register(spec: ScoringSpec) -> ScoringSpec:
    """Register a spec under ``spec.name`` (+ aliases); returns it."""
    if spec.name in _REGISTRY or spec.name in _ALIASES:
        raise ValueError(f"scoring method {spec.name!r} already registered")
    for a in spec.aliases:
        if a in _REGISTRY or a in _ALIASES:
            raise ValueError(f"scoring alias {a!r} already registered")
    _REGISTRY[spec.name] = spec
    for a in spec.aliases:
        _ALIASES[a] = spec.name
    return spec


def registered_methods() -> Dict[str, ScoringSpec]:
    """Registered specs by canonical name (sorted, aliases excluded)."""
    return dict(sorted(_REGISTRY.items()))


def scoring_usage() -> str:
    """One line per registered method: name, family, rel_dim rule, formula."""
    lines = []
    for name, spec in registered_methods().items():
        lines.append(
            f"  {name}  [{spec.family}] rel_dim={spec.rel_dim_doc}"
            f"  — {spec.doc}"
        )
    return "\n".join(lines)


def get_scoring(method: str) -> ScoringSpec:
    """Look up a registered scoring method by (canonical or alias) name.

    Unknown names raise a ``ValueError`` listing every registered method —
    the registry is the single source of truth the CLI (``--method``), the
    engines, and the eval-kernel dispatch all lean on.
    """
    canonical = _ALIASES.get(method, method)
    spec = _REGISTRY.get(canonical)
    if spec is None:
        raise ValueError(
            f"unknown scoring method {method!r}; registered methods:\n"
            f"{scoring_usage()}"
        )
    return spec


def parse_method(method: str) -> str:
    """Validate a ``--method`` name; returns the canonical name."""
    return get_scoring(method).name


# ------------------------------------------------------------- score pieces
def _split_complex(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Split the last dim into (re, im) halves."""
    half = x.shape[-1] // 2
    return x[..., :half], x[..., half:]


def transe_score(
    h: jnp.ndarray, r: jnp.ndarray, t: jnp.ndarray, gamma: float
) -> jnp.ndarray:
    """gamma - ||h + r - t||_2 ; broadcasts over leading dims."""
    return gamma - jnp.linalg.norm(h + r - t, axis=-1)


def rotate_score(
    h: jnp.ndarray, phase: jnp.ndarray, t: jnp.ndarray, gamma: float
) -> jnp.ndarray:
    """gamma - || h o r - t ||  with r = exp(i * phase), h,t in C^{d/2}."""
    h_re, h_im = _split_complex(h)
    t_re, t_im = _split_complex(t)
    r_re, r_im = jnp.cos(phase), jnp.sin(phase)
    d_re = h_re * r_re - h_im * r_im - t_re
    d_im = h_re * r_im + h_im * r_re - t_im
    # RotatE uses the sum of complex moduli (L2 over the (re,im) pair, L1 over
    # coordinates).
    dist = jnp.sqrt(d_re**2 + d_im**2 + 1e-12).sum(axis=-1)
    return gamma - dist


def _phase_scale(gamma: float, dim: int) -> float:
    """Entity-embedding -> phase-unit scale: embedding_range / pi.

    pRotatE interprets entity coordinates as angles; the RotatE reference
    divides by ``embedding_range / pi`` so a full init range spans one turn.
    ``embedding_range`` is reconstructed from gamma with the paper's fixed
    epsilon, keeping the score a pure function of (arrays, gamma).
    """
    return (gamma + DEFAULT_EPSILON) / dim / float(jnp.pi)


def _protate_modulus(gamma: float, dim: int) -> float:
    """pRotatE distance weight: 0.5 * embedding_range (the RotatE reference
    learns this scalar from that init; we keep it fixed and stateless)."""
    return 0.5 * (gamma + DEFAULT_EPSILON) / dim


def protate_score(
    h: jnp.ndarray, phase: jnp.ndarray, t: jnp.ndarray, gamma: float
) -> jnp.ndarray:
    """gamma - modulus * sum_j |sin(h_j/s + phase_j - t_j/s)| (pRotatE)."""
    dim = h.shape[-1]
    s = _phase_scale(gamma, dim)
    d = jnp.sin(h / s + phase - t / s)
    return gamma - jnp.abs(d).sum(axis=-1) * _protate_modulus(gamma, dim)


def distmult_score(
    h: jnp.ndarray, r: jnp.ndarray, t: jnp.ndarray, gamma: float = 0.0
) -> jnp.ndarray:
    """<h, r, t> = sum_j h_j r_j t_j (DistMult trilinear product)."""
    del gamma
    return (h * r * t).sum(axis=-1)


def _ccorr(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Circular correlation ccorr(a, b)_k = sum_i a_i b_{(i+k) mod n}."""
    n = a.shape[-1]
    return jnp.fft.irfft(jnp.conj(jnp.fft.rfft(a)) * jnp.fft.rfft(b), n=n)


def _cconv(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Circular convolution cconv(a, b)_k = sum_i a_i b_{(k-i) mod n}."""
    n = a.shape[-1]
    return jnp.fft.irfft(jnp.fft.rfft(a) * jnp.fft.rfft(b), n=n)


def hole_score(
    h: jnp.ndarray, r: jnp.ndarray, t: jnp.ndarray, gamma: float = 0.0
) -> jnp.ndarray:
    """<r, ccorr(h, t)> (HolE holographic embedding score)."""
    del gamma
    return (r * _ccorr(h, t)).sum(axis=-1)


def proje_score(
    h: jnp.ndarray, r: jnp.ndarray, t: jnp.ndarray, gamma: float = 0.0
) -> jnp.ndarray:
    """<tanh(h + r), t> (ProjE pointwise combination, bias-free)."""
    del gamma
    return (jnp.tanh(h + r) * t).sum(axis=-1)


def complex_score(
    h: jnp.ndarray, r: jnp.ndarray, t: jnp.ndarray, gamma: float = 0.0
) -> jnp.ndarray:
    """Re(<h, r, conj(t)>)."""
    del gamma
    h_re, h_im = _split_complex(h)
    r_re, r_im = _split_complex(r)
    t_re, t_im = _split_complex(t)
    return (
        (h_re * r_re * t_re)
        + (h_im * r_re * t_im)
        + (h_re * r_im * t_im)
        - (h_im * r_im * t_re)
    ).sum(axis=-1)


# ------------------------------------------------- per-leg candidate queries
# Each returns (q_tail, q_head) such that scoring candidate c as tail equals
# kernel(q_tail, c) and as head equals kernel(q_head, c) — the algebra that
# lets filtered-ranking eval share ONE candidate block across the batch
# (kernels/kge_score.py + kernels/bilinear_score.py docstrings).
def _transe_queries(h, r, t, gamma):
    del gamma
    return h + r, t - r  # ||(h+r) - c|| ; ||c + r - t|| == ||c - (t - r)||


def _rotate_queries(h, phase, t, gamma):
    del gamma
    cos, sin = jnp.cos(phase), jnp.sin(phase)
    h_re, h_im = _split_complex(h)
    t_re, t_im = _split_complex(t)
    # tail: |h∘r - c|; head: |c∘r - t| == |c - t∘conj(r)|
    q_t = jnp.concatenate([h_re * cos - h_im * sin,
                           h_re * sin + h_im * cos], axis=-1)
    q_h = jnp.concatenate([t_re * cos + t_im * sin,
                           t_im * cos - t_re * sin], axis=-1)
    return q_t, q_h


def _protate_queries(h, phase, t, gamma):
    s = _phase_scale(gamma, h.shape[-1])
    # |sin(ph_h + r - ph_c)| == |sin(q_t - ph_c)|; |sin(ph_c + r - ph_t)| ==
    # |sin(q_h - ph_c)| by sign symmetry of |sin|.  cand_prep rescales the
    # candidate block to the same phase units once per kernel call.
    return h / s + phase, t / s - phase


def _protate_cand_prep(cand, gamma):
    return cand / _phase_scale(gamma, cand.shape[-1])


def _distmult_queries(h, r, t, gamma):
    del gamma
    return h * r, t * r  # <h,r,c> = (h*r)·c ; <c,r,t> = (t*r)·c


def _hole_queries(h, r, t, gamma):
    del gamma
    # <r, ccorr(h,c)> == <cconv(h,r), c> and <r, ccorr(c,t)> == <ccorr(r,t), c>
    # (swap the summation order) — both legs reduce to q · cand, so HolE
    # rides the bilinear eval kernel with no candidate transform.
    return _cconv(h, r), _ccorr(r, t)


def _proje_queries(h, r, t, gamma):
    del gamma, t
    # tail: <tanh(h+r), c> folds to q_t · c — but the head leg
    # <tanh(c+r), t> is nonlinear IN THE CANDIDATE, so no head query row
    # exists (fold_head=False routes that leg through the exact broadcast).
    return jnp.tanh(h + r), None


def _complex_queries(h, r, t, gamma):
    del gamma
    h_re, h_im = _split_complex(h)
    r_re, r_im = _split_complex(r)
    t_re, t_im = _split_complex(t)
    # Re(<h,r,conj(c)>) as a function of c: coefficients of (c_re, c_im);
    # Re(<c,r,conj(t)>) likewise — both legs become q · [c_re, c_im].
    q_t = jnp.concatenate([h_re * r_re - h_im * r_im,
                           h_im * r_re + h_re * r_im], axis=-1)
    q_h = jnp.concatenate([r_re * t_re + r_im * t_im,
                           r_re * t_im - r_im * t_re], axis=-1)
    return q_t, q_h


register(ScoringSpec(
    name="transe", family="distance",
    doc="gamma - ||h + r - t||_2",
    score=transe_score, rel_dim=lambda dim: dim, rel_dim_doc="dim",
    rel_init="uniform", cand_queries=_transe_queries, adversarial=True,
    kernel_mode="transe",
))
register(ScoringSpec(
    name="rotate", family="distance",
    doc="gamma - |h ∘ e^{i phase} - t| (entities in C^{dim/2})",
    score=rotate_score, rel_dim=lambda dim: dim // 2, rel_dim_doc="dim/2",
    rel_init="phase", cand_queries=_rotate_queries, adversarial=True,
    kernel_mode="rotate",
))
register(ScoringSpec(
    name="protate", family="distance",
    doc="gamma - m * sum|sin(h/s + phase - t/s)| (phase-only RotatE)",
    score=protate_score, rel_dim=lambda dim: dim, rel_dim_doc="dim",
    rel_init="phase", cand_queries=_protate_queries, adversarial=True,
    cand_prep=_protate_cand_prep, kernel_mode="protate",
    kernel_statics=lambda gamma, dim: {"modulus": _protate_modulus(gamma, dim)},
    aliases=("prot",),
))
register(ScoringSpec(
    name="distmult", family="bilinear",
    doc="sum_j h_j r_j t_j (symmetric trilinear product)",
    score=distmult_score, rel_dim=lambda dim: dim, rel_dim_doc="dim",
    rel_init="uniform", cand_queries=_distmult_queries, adversarial=False,
))
register(ScoringSpec(
    name="complex", family="bilinear",
    doc="Re(<h, r, conj(t)>) (entities and relations in C^{dim/2})",
    score=complex_score, rel_dim=lambda dim: dim, rel_dim_doc="dim",
    rel_init="uniform", cand_queries=_complex_queries, adversarial=False,
))
register(ScoringSpec(
    name="proje", family="bilinear",
    doc="<tanh(h + r), t> (ProjE pointwise combination; head leg unfolds)",
    score=proje_score, rel_dim=lambda dim: dim, rel_dim_doc="dim",
    rel_init="uniform", cand_queries=_proje_queries, adversarial=False,
    fold_head=False,
))
register(ScoringSpec(
    name="hole", family="bilinear",
    doc="<r, ccorr(h, t)> (holographic circular correlation)",
    score=hole_score, rel_dim=lambda dim: dim, rel_dim_doc="dim",
    rel_init="uniform", cand_queries=_hole_queries, adversarial=False,
))


# ------------------------------------------------------------ model + init
@dataclasses.dataclass(frozen=True)
class KGEModel:
    """Static description of a KGE scoring model."""

    method: Method
    num_entities: int
    num_relations: int
    dim: int  # entity embedding dimension (real parameter count per entity)
    gamma: float = DEFAULT_GAMMA
    epsilon: float = DEFAULT_EPSILON

    def __post_init__(self):
        get_scoring(self.method)  # unknown method -> registry error, eagerly

    @property
    def spec(self) -> ScoringSpec:
        return get_scoring(self.method)

    @property
    def rel_dim(self) -> int:
        return self.spec.rel_dim(self.dim)

    @property
    def embedding_range(self) -> float:
        return (self.gamma + self.epsilon) / self.dim


def init_kge_params(key: jax.Array, model: KGEModel) -> dict:
    """Uniform init in [-embedding_range, embedding_range] as in RotatE/FedE;
    phase-valued relations (RotatE, pRotatE) draw uniformly in [-pi, pi]."""
    k_e, k_r = jax.random.split(key)
    rng = model.embedding_range
    ent = jax.random.uniform(
        k_e, (model.num_entities, model.dim), minval=-rng, maxval=rng
    )
    if model.spec.rel_init == "phase":
        rel = jax.random.uniform(
            k_r, (model.num_relations, model.rel_dim), minval=-jnp.pi, maxval=jnp.pi
        )
    else:
        rel = jax.random.uniform(
            k_r, (model.num_relations, model.rel_dim), minval=-rng, maxval=rng
        )
    return {"entity": ent, "relation": rel}


# ---------------------------------------------------------- scoring + loss
def get_score_fn(method: Method):
    """Score function operating directly on embedding rows (h, r, t, gamma).

    Used by callers that manage their own gathers — e.g. the fused trainer in
    :mod:`repro.core.state`, which gathers each batch's rows ONCE and
    differentiates with respect to the gathered rows instead of the full
    table (one dense scatter-add per step instead of one per gather).
    """
    return get_scoring(method).score


def score_triples(
    params: dict,
    heads: jnp.ndarray,
    relations: jnp.ndarray,
    tails: jnp.ndarray,
    method: Method,
    gamma: float = DEFAULT_GAMMA,
) -> jnp.ndarray:
    """Score index triples.  heads/relations/tails broadcast together.

    ``heads``/``tails`` may have an extra negatives axis, e.g.
    heads (B,), relations (B,), tails (B, N) -> scores (B, N).
    """
    h = params["entity"][heads]
    r = params["relation"][relations]
    t = params["entity"][tails]
    if t.ndim == h.ndim + 1:  # negatives on the tail side
        h = h[..., None, :]
        r = r[..., None, :]
    elif h.ndim == t.ndim + 1:  # negatives on the head side
        t = t[..., None, :]
        r = r[..., None, :]
    return get_scoring(method).score(h, r, t, gamma)


def kge_loss(
    params: dict,
    pos: jnp.ndarray,  # (B, 3) int32 (h, r, t)
    neg_tails: jnp.ndarray,  # (B, N) int32 corrupted tails
    neg_heads: jnp.ndarray,  # (B, N) int32 corrupted heads
    method: Method,
    gamma: float = DEFAULT_GAMMA,
    adversarial_temperature: float = 1.0,
) -> jnp.ndarray:
    """Self-adversarial negative sampling loss (RotatE Eq. 5, used by FedE).

    L = -log sigma(pos_score) - sum_i w_i log sigma(-neg_score_i)
    with w_i = softmax(neg_score_i * temperature), stop-gradiented.
    The bilinear family uses the same loss on its trilinear scores (FedE
    convention) with uniform weighting; self-adversarial weighting applies
    to the distance family (paper: temp 1) — :attr:`ScoringSpec.adversarial`.
    """
    h, r, t = pos[:, 0], pos[:, 1], pos[:, 2]
    pos_score = score_triples(params, h, r, t, method, gamma)  # (B,)
    neg_t_score = score_triples(params, h, r, neg_tails, method, gamma)  # (B, N)
    neg_h_score = score_triples(params, neg_heads, r, t, method, gamma)  # (B, N)
    neg_score = jnp.concatenate([neg_t_score, neg_h_score], axis=-1)  # (B, 2N)
    return loss_from_scores(pos_score, neg_score, method, adversarial_temperature)


def per_sample_losses(
    pos_score: jnp.ndarray,  # (..., B)
    neg_score: jnp.ndarray,  # (..., B, 2N)
    method: Method,
    adversarial_temperature: float = 1.0,
) -> jnp.ndarray:
    """Per-sample ``pos_loss + neg_loss`` (NOT yet halved/averaged)."""
    if get_scoring(method).adversarial and adversarial_temperature > 0:
        w = jax.nn.softmax(
            jax.lax.stop_gradient(neg_score) * adversarial_temperature, axis=-1
        )
    else:
        w = jnp.full_like(neg_score, 1.0 / neg_score.shape[-1])

    pos_loss = -jax.nn.log_sigmoid(pos_score)
    neg_loss = -(w * jax.nn.log_sigmoid(-neg_score)).sum(axis=-1)
    return pos_loss + neg_loss


def loss_from_scores(
    pos_score: jnp.ndarray,  # (B,)
    neg_score: jnp.ndarray,  # (B, 2N)
    method: Method,
    adversarial_temperature: float = 1.0,
    sample_weight: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """The self-adversarial loss given already-computed scores (see
    :func:`kge_loss` for the semantics; split out so gather-once trainers can
    reuse the exact weighting/averaging logic)."""
    per_sample = per_sample_losses(
        pos_score, neg_score, method, adversarial_temperature
    )
    if sample_weight is None:
        return per_sample.mean() / 2.0
    sw = sample_weight.astype(per_sample.dtype)
    return (per_sample * sw).sum() / jnp.maximum(sw.sum(), 1.0) / 2.0
