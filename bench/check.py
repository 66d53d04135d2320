"""The comparison of the timed path's output with the plain reference.

Train cells compare the first ISM span (the sparse rounds and the sync
round), which set-up drives through the window's own calls: each round's
loss per client, the first moment of Adam after round one (the gradient as
the optimizer holds it), the change of every table over the span, the
change of each client's upload history (the rows it sent, all of its shared
rows at the sync), and the federation's filtered MRR at the eval boundary
that closes the span.  Reported beside them and not compared: the worst
client's MRR gap, which one rank moved by one place can swing by percents
at the MRR of a federation five rounds in, and the sparse rounds' download
counts the ledger bills, which no control or planted fault moved (a count
is the lesser of K and the rows others sent, whatever the precision).  Eval cells compare the metric block of every call in the
window.

A norm is compared by its worst leaf, a leaf being one client's entity or
relation table: the gap between the program's norm and the reference's,
over the larger of the reference's norm of that leaf and of the median
leaf.  Leaves whose reference first moment is under a thousandth of the
median leaf's take no part in the change.
"""
from __future__ import annotations

import numpy as np

LEAVES = ("entity", "relation")


def _leaf_norms(tables, rows) -> list[float]:
    """Norms of each client's tables: ``tables`` ``{name: (C, R_max, D)}``
    or a list of per-client ``{name: (R_c, D)}``; ``rows`` the true counts."""
    out = []
    for c, n in enumerate(rows):
        for name in LEAVES:
            t = np.asarray(tables[c][name] if isinstance(tables, list) else tables[name][c],
                           np.float64)
            out.append(float(np.linalg.norm(t[: n[name]])))
    return out


def norm_gap(prog: list[float], ref: list[float], keep=None) -> float:
    """Worst-leaf gap of two lists of leaf norms."""
    prog, ref = np.asarray(prog), np.asarray(ref)
    keep = np.ones(ref.shape, bool) if keep is None else np.asarray(keep)
    med = float(np.median(ref))
    gap = np.abs(prog - ref) / np.maximum(np.maximum(ref, med), 1e-30)
    return float(gap[keep].max())


def _sub(a, b):
    if isinstance(a, list):
        return [{k: np.asarray(x[k], np.float64) - np.asarray(y[k], np.float64)
                 for k in LEAVES} for x, y in zip(a, b)]
    return {k: np.asarray(a[k], np.float64) - np.asarray(b[k], np.float64)
            for k in LEAVES}


def mrr_gap(prog_block, ref_block) -> float:
    """Worst client's relative gap of filtered MRR."""
    p, r = np.asarray(prog_block)[:, 0], np.asarray(ref_block)[:, 0]
    return float(np.max(np.abs(p - r) / np.maximum(np.abs(r), 1e-30)))


def federation_mrr_gap(prog_block, ref_block) -> float:
    """Relative gap of the federation's filtered MRR: the clients' MRR
    weighted by their ranked triples, as an eval boundary reports it."""
    p, r = np.asarray(prog_block, np.float64), np.asarray(ref_block, np.float64)
    mp = np.sum(p[:, 0] * p[:, 4]) / np.sum(p[:, 4])
    mr = np.sum(r[:, 0] * r[:, 4]) / np.sum(r[:, 4])
    return float(abs(mp - mr) / max(abs(mr), 1e-30))


def hits_gap(prog_block, ref_block) -> float:
    """Worst client's and cutoff's gap of Hits@{1,3,10}, in ranks."""
    p, r = np.asarray(prog_block), np.asarray(ref_block)
    legs = 2.0 * r[:, 4:5]
    return float(np.max(np.abs(p[:, 1:4] - r[:, 1:4]) * legs))


def _hist_change(h0, h3, shared) -> list[float]:
    return [float(np.linalg.norm(np.asarray(h3[c][:n], np.float64)
                                 - np.asarray(h0[c][:n], np.float64)))
            for c, n in enumerate(shared)]


def train_numbers(prog: dict, ref: dict, rows) -> dict:
    """``prog`` and ``ref`` hold ``loss`` (rounds, C), ``down`` (sparse
    rounds, C), ``params0``, ``mu1``, ``params_end``, ``hist0``,
    ``hist_end`` and ``eval`` ((C, 5) block); ``rows`` per client
    ``{"entity": E_c, "relation": R}``.
    The reference's ``hist0`` gives each client's count of shared rows."""
    shared = [len(h) for h in ref["hist0"]]
    loss_p, loss_r = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    mu_p, mu_r = _leaf_norms(prog["mu1"], rows), _leaf_norms(ref["mu1"], rows)
    keep = np.asarray(mu_r) >= 1e-3 * float(np.median(mu_r))
    ch_p = _leaf_norms(_sub(prog["params_end"], prog["params0"]), rows)
    ch_r = _leaf_norms(_sub(ref["params_end"], ref["params0"]), rows)
    return {
        "loss_gap": float(np.max(np.abs(loss_p - loss_r) / np.abs(loss_r))),
        "grad_gap": norm_gap(mu_p, mu_r),
        "change_gap": norm_gap(ch_p, ch_r, keep),
        "upload_gap": norm_gap(_hist_change(prog["hist0"], prog["hist_end"], shared),
                               _hist_change(ref["hist0"], ref["hist_end"], shared)),
        "eval_gap": federation_mrr_gap(prog["eval"], ref["eval"]),
        "eval_client_gap": mrr_gap(prog["eval"], ref["eval"]),
        "down_gap": float(np.max(np.abs(np.asarray(prog["down"], np.int64)
                                        - np.asarray(ref["down"], np.int64)))),
    }


def eval_numbers(blocks, ref_block) -> dict:
    """Every block the window produced against the reference's."""
    return {
        "mrr_gap": max(mrr_gap(b, ref_block) for b in blocks),
        "hits_gap": max(hits_gap(b, ref_block) for b in blocks),
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number that has a limit against it; one that is not finite
    fails."""
    out, ok = {}, True
    for name in limits:
        value, limit = numbers[name], float(limits[name]["limit"])
        fine = bool(np.isfinite(value)) and value <= limit
        ok &= fine
        out[name] = {"value": value, "limit": limit}
    return ok, out
