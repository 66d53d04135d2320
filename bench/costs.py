"""Operations and bytes of the work the benchmark's cells do, from shapes.

Each count describes the logical work, whatever implements it: a kernel's
operands read once and its result written once, never its tiling.  Floats
are 4 bytes.
"""
from __future__ import annotations

import json
import os

F32 = 4

# floating-point operations of one score, per method, at width ``d``:
# TransE gamma - ||h + r - t||: add, subtract, square, sum (4d) and a root;
# ComplEx Re<h, r, conj t>: four triple products and three adds per
# complex coordinate, then the sum over the d/2 coordinates (6d);
# RotatE gamma - sum |h e^{i phase} - t|: per complex coordinate the
# rotation (4 products, 2 adds), the subtraction of t (2), the squares and
# their add (3), the root (1) and the sum (1), 13 over d/2 coordinates
# (6.5d); the phase's sine and cosine are per relation, not per score.
SCORE_FLOPS = {
    "transe": lambda d: 4 * d + 1,
    "complex": lambda d: 6 * d,
    "rotate": lambda d: 6.5 * d,
}

# one candidate scored against a query row the eval folds beforehand:
# distance family ||q - c|| (subtract, square, sum: 3d, and a root);
# bilinear family q . c (2d); RotatE sum |q - c| with q the rotated query,
# per complex coordinate the subtraction (2), the squares and their add
# (3), the root (1) and the sum (1), 7 over d/2 coordinates (3.5d).
CAND_FLOPS = {
    "transe": lambda d: 3 * d + 1,
    "complex": lambda d: 2 * d,
    "rotate": lambda d: 3.5 * d,
}


def train_flops(method: str, dim: int, real_triples: int, negatives: int) -> float:
    """Forward and backward scoring operations of ``real_triples`` positive
    triples, each with ``negatives`` corrupted tails and as many heads;
    backward is counted as twice the forward."""
    return 3.0 * real_triples * (1 + 2 * negatives) * SCORE_FLOPS[method](dim)


def eval_flops(method: str, dim: int, pairs: int) -> float:
    """Scoring operations of ranking, on both legs, ``pairs`` (triple,
    candidate entity) pairs: each triple against its client's entities."""
    return 2.0 * pairs * CAND_FLOPS[method](dim)


def change_score_cost(rows: int, dim: int) -> tuple[float, float]:
    """``1 - cos`` of ``rows`` pairs of rows: three dot products per row;
    reads both row blocks, writes one score per row."""
    return rows * (6.0 * dim + 4), F32 * (2.0 * rows * dim + rows)


def sparse_apply_cost(rows: int, dim: int) -> tuple[float, float]:
    """Eq. 4, ``(A + E) / (1 + P)`` where selected: reads the table, the
    aggregate, the priorities and the int8 signs, writes the table."""
    return rows * (2.0 * dim + 1), F32 * (3.0 * rows * dim + rows) + rows


def cand_score_cost(method: str, queries: int, cands: int, dim: int) -> tuple[float, float]:
    """One eval-kernel call: ``queries`` rows against ``cands`` candidate
    rows; reads both, writes the ``(queries, cands)`` score block."""
    return (queries * cands * CAND_FLOPS[method](dim),
            F32 * (queries * dim + cands * dim + queries * cands))


def cand_kernel_roofline(ctx: dict, op: str) -> float | None:
    """An eval kernel's share of its roofline, in %, from a metric reader's
    ``ctx``.  Per eval call and leg (tail, head), each client's real
    queries are scored against its real entities: the queries and candidate
    rows read once and the score block written once.  None where no op
    named ``op`` ran."""
    cfg, sh, tr = ctx["cfg"], ctx["shapes"], ctx["trace"]
    if not tr.op_calls(op):
        return None
    flops = nbytes = 0.0
    for q, e in zip(sh["queries"], sh["entities"]):
        f, b = cand_score_cost(cfg["method"], q, e, cfg["dim"])
        flops, nbytes = flops + 2 * f, nbytes + 2 * b
    n = ctx["work"]["calls"]
    return roofline_pct(n * flops, n * nbytes, tr.op_s(op), ctx["peak"])


def peaks(device_kind: str, path: str | None = None) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(table)}"
        )
    return table[device_kind]


def roofline_pct(flops: float, nbytes: float, seconds: float, peak: dict) -> float | None:
    """Share of the roofline, in %: the least time the chip could take
    (operations over peak FLOP/s or bytes over peak bandwidth, whichever
    is longer) over the time taken.  None where nothing was timed."""
    if seconds <= 0:
        return None
    floor = max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * floor / seconds
