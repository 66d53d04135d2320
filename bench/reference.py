"""Plain reference of the FedS federation, for the check that decides ``correct``.

It imports nothing of the program and takes nothing the program made: the
initial tables, the batches, the negatives and the tie-break jitter are
drawn again here from the run's seed, by the sampling rules the program
states (``jax.random`` keys folded per client and per purpose).  Each
client keeps its own unpadded tables; training is one ``lax.scan`` of
plain Adam steps over exactly the client's own batches, with the gradient
taken through the table indexing; the FedS round (Eq. 1-4) is written out
per client in array operations, and the sync round as the full exchange
FedE specifies (every shared row replaced by its mean over the clients
that hold it); ranking scores every candidate directly.

Methods (``METHODS``): TransE and ComplEx as this reproduction states them
(both tables ``dim`` wide, uniform in +-(gamma + 2) / dim), and RotatE as
its published code defines it (Sun et al., ICLR 2019): ``hidden = dim/2``
complex coordinates, entities ``(E, dim)`` and relations ``(R, hidden)``
both uniform in +-range, ``range = (gamma + 2) / hidden``, the relation
read as the phase ``r / (range / pi)``.  Every method scores each step's
corrupted tails and heads together, one softmax over both sides' negatives;
the published RotatE alternates head-batch and tail-batch steps instead.

Blocking: where one side's ``(B, N, D)`` float32 block of negative rows is
over ``BUDGET``, a training step runs in blocks along the negative axis
(every score first, holding no residuals, for the self-adversarial
weights; then each block's weighted term differentiated through the table
indexing and summed), ranking scores ``min(32, BUDGET // (E*D*4))`` rows at
a time, and where one side's rows of a leg's filtered pairs are over
``BUDGET``, they are scored ``BUDGET // (2*D*4)`` pairs at a time.  Loss,
gradient and ranks are the same; only the order of the sums changes.
Under the budget the step, ranking and filter run unblocked.

``dtype`` runs the whole reference in another float type: bfloat16 is the
control, the precision a later change might be tempted to train in.
``half_batch`` plants a fault: each step's loss is the mean over the first
half of the batch only.  ``sync_as_sparse`` plants another: the sync round
runs as one more sparse round.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8
EPSILON = 2.0  # init range (gamma + EPSILON) / width, as FedE and RotatE
BUDGET = 1 << 30  # bytes of float32 negative or candidate rows held at once


def transe(h, r, t, gamma):
    return gamma - jnp.sqrt(jnp.sum(jnp.square(h + r - t), axis=-1))


def complex_(h, r, t, gamma):
    d = h.shape[-1] // 2
    hr, hi = h[..., :d], h[..., d:]
    rr, ri = r[..., :d], r[..., d:]
    tr, ti = t[..., :d], t[..., d:]
    return jnp.sum(hr * rr * tr + hi * rr * ti + hr * ri * ti - hi * ri * tr, axis=-1)


def rotate(h, r, t, gamma):
    """gamma - sum_j |h_j e^{i phase_j} - t_j| over the ``hidden`` complex
    coordinates; ``r`` in embedding units, ``phase = r / (range / pi)``."""
    d = r.shape[-1]
    phase = r / ((gamma + EPSILON) / d / math.pi)
    hr, hi = h[..., :d], h[..., d:]
    tr, ti = t[..., :d], t[..., d:]
    re = hr * jnp.cos(phase) - hi * jnp.sin(phase) - tr
    im = hr * jnp.sin(phase) + hi * jnp.cos(phase) - ti
    return gamma - jnp.sum(jnp.sqrt(re * re + im * im), axis=-1)


@dataclasses.dataclass(frozen=True)
class Method:
    """A scoring method: its score ``(h, r, t, gamma)``, whether negatives
    are weighted self-adversarially (else uniformly), the relation table's
    width from ``dim``, and the init range from ``(gamma, dim)``: both
    tables are drawn uniform in +-range."""

    score: Callable
    adversarial: bool
    rel_width: Callable[[int], int]
    init_range: Callable[[float, int], float]


def _range_over_dim(gamma, dim):
    return (gamma + EPSILON) / dim


METHODS = {
    "transe": Method(transe, True, lambda dim: dim, _range_over_dim),
    "complex": Method(complex_, False, lambda dim: dim, _range_over_dim),
    "rotate": Method(rotate, True, lambda dim: dim // 2,
                     lambda gamma, dim: (gamma + EPSILON) / (dim // 2)),
}


def init_tables(seed: int, cid: int, num_entities: int, num_relations: int,
                dim: int, gamma: float, dtype, method: str):
    """A client's initial tables, by the method's width and range rules."""
    m = METHODS[method]
    k_e, k_r = jax.random.split(jax.random.PRNGKey(seed * 9973 + cid))
    lim = m.init_range(gamma, dim)
    ent = jax.random.uniform(k_e, (num_entities, dim), minval=-lim, maxval=lim)
    rel = jax.random.uniform(k_r, (num_relations, m.rel_width(dim)),
                             minval=-lim, maxval=lim)
    return {"entity": ent.astype(dtype), "relation": rel.astype(dtype)}


def neg_block(batch: int, negatives: int, dim: int, budget: int = BUDGET) -> int:
    """Negatives a training step takes at once: all of them where one
    side's ``(B, N, D)`` float32 block fits ``budget``; else the largest
    divisor of N whose blocks of both sides fit it together (at least 1)."""
    if batch * negatives * dim * 4 <= budget:
        return negatives
    fits = [n for n in range(1, negatives + 1)
            if negatives % n == 0 and 2 * batch * n * dim * 4 <= budget]
    return max(fits, default=1)


def _blocked_value_and_grad(p, tri, nt, nh, *, score, adversarial, gamma,
                            temp, rows, nb):
    """Loss and gradient of one step, ``nb`` negatives a side at a time.

    Pass 1 scores every negative, differentiating nothing, and fixes the
    negative weights; pass 2 takes each block's weighted term's gradient
    through the table indexing and adds it up.  Row ``i`` of the batch
    counts ``1 / (2 rows)`` where ``i < rows``, as ``mean(per[:rows]) / 2``.
    """
    b, n = nt.shape
    blocks = n // nb
    row_w = jnp.where(jnp.arange(b) < rows, 0.5 / rows, 0.0)
    nt_b = nt.reshape(b, blocks, nb).transpose(1, 0, 2)
    nh_b = nh.reshape(b, blocks, nb).transpose(1, 0, 2)

    def rows_of(q):
        ent, rel = q["entity"], q["relation"]
        return ent, ent[tri[:, 0]], rel[tri[:, 1]], ent[tri[:, 2]]

    def sides(q, nt_j, nh_j):
        ent, h, r, t = rows_of(q)
        return (score(h[:, None], r[:, None], ent[nt_j], gamma),
                score(ent[nh_j], r[:, None], t[:, None], gamma))

    _, h, r, t = rows_of(p)
    pos_s = score(h, r, t, gamma)
    st, sh = jax.lax.map(lambda x: sides(p, *x), (nt_b, nh_b))
    neg_s = jnp.concatenate([st.transpose(1, 0, 2).reshape(b, n),
                             sh.transpose(1, 0, 2).reshape(b, n)], axis=-1)
    if adversarial:
        w = jax.nn.softmax(neg_s * temp, axis=-1)
    else:
        w = jnp.full_like(neg_s, 1.0 / neg_s.shape[-1])
    per = -jax.nn.log_sigmoid(pos_s) - jnp.sum(
        w * jax.nn.log_sigmoid(-neg_s), axis=-1)
    loss = jnp.sum(per[:rows]) / rows / 2.0
    w_t = w[:, :n].reshape(b, blocks, nb).transpose(1, 0, 2)
    w_h = w[:, n:].reshape(b, blocks, nb).transpose(1, 0, 2)

    def pos_term(q):
        _, h, r, t = rows_of(q)
        return jnp.sum(row_w * -jax.nn.log_sigmoid(score(h, r, t, gamma)))

    def block_term(q, nt_j, nh_j, wt_j, wh_j):
        a, c = sides(q, nt_j, nh_j)
        return -jnp.sum(row_w[:, None] * (wt_j * jax.nn.log_sigmoid(-a)
                                         + wh_j * jax.nn.log_sigmoid(-c)))

    def add_block(g, x):
        gj = jax.grad(block_term)(p, *x)
        return jax.tree.map(jnp.add, g, gj), None

    g, _ = jax.lax.scan(add_block, jax.grad(pos_term)(p),
                        (nt_b, nh_b, w_t, w_h))
    return loss, g


@functools.partial(
    jax.jit,
    static_argnames=("method", "gamma", "lr", "temp", "half_batch", "budget"),
)
def train_client(params, mu, nu, step, pos, neg_t, neg_h, *, method, gamma,
                 lr, temp, half_batch, budget=BUDGET):
    """Adam steps over ``pos`` ``(S, B, 3)``; returns the new state and the
    mean loss of the steps."""
    spec = METHODS[method]
    score, adversarial = spec.score, spec.adversarial

    def loss_fn(p, tri, nt, nh):
        ent, rel = p["entity"], p["relation"]
        h, r, t = ent[tri[:, 0]], rel[tri[:, 1]], ent[tri[:, 2]]
        pos_s = score(h, r, t, gamma)
        neg_s = jnp.concatenate([
            score(h[:, None], r[:, None], ent[nt], gamma),
            score(ent[nh], r[:, None], t[:, None], gamma),
        ], axis=-1)
        if adversarial:
            w = jax.nn.softmax(jax.lax.stop_gradient(neg_s) * temp, axis=-1)
        else:
            w = jnp.full_like(neg_s, 1.0 / neg_s.shape[-1])
        per = -jax.nn.log_sigmoid(pos_s) - jnp.sum(
            w * jax.nn.log_sigmoid(-neg_s), axis=-1
        )
        if half_batch:
            per = per[: per.shape[0] // 2]
        return jnp.mean(per) / 2.0

    b, n = neg_t.shape[1:]
    nb = neg_block(b, n, params["entity"].shape[1], budget)
    if nb == n:
        value_and_grad = jax.value_and_grad(loss_fn)
    else:
        value_and_grad = functools.partial(
            _blocked_value_and_grad, score=score, adversarial=adversarial,
            gamma=gamma, temp=temp, rows=b // 2 if half_batch else b, nb=nb)

    def step_fn(carry, x):
        p, m, v, k = carry
        loss, g = value_and_grad(p, *x)
        k = k + 1
        m = jax.tree.map(lambda a, b: B1 * a + (1 - B1) * b, m, g)
        v = jax.tree.map(lambda a, b: B2 * a + (1 - B2) * b * b, v, g)
        c1 = 1 - B1 ** k.astype(jnp.float32)
        c2 = 1 - B2 ** k.astype(jnp.float32)
        p = jax.tree.map(
            lambda w_, a, b: (w_ - lr * (a / c1) / (jnp.sqrt(b / c2) + EPS)).astype(w_.dtype),
            p, m, v,
        )
        return (p, m, v, k), loss

    (params, mu, nu, step), losses = jax.lax.scan(
        step_fn, (params, mu, nu, step), (pos, neg_t, neg_h)
    )
    return params, mu, nu, step, jnp.mean(losses.astype(jnp.float32))


def top_k(x, k: int):
    """Indices of the ``k`` largest, ties to the lower index."""
    return jnp.argsort(-x, stable=True)[:k]


@functools.partial(jax.jit, static_argnames=("shape", "negatives", "num_entities"))
def _draw(kb, c, train, keep, *, shape, negatives, num_entities):
    """Client ``c``'s positives and uniform negatives: ``shape`` =
    (steps, batch) draws, of which the rows ``keep`` are its own steps."""
    kc = jax.random.fold_in(kb, c)
    pi = jax.random.randint(jax.random.fold_in(kc, 1), shape, 0, train.shape[0])
    nt = jax.random.randint(jax.random.fold_in(kc, 2), shape + (negatives,), 0,
                            num_entities)
    nh = jax.random.randint(jax.random.fold_in(kc, 3), shape + (negatives,), 0,
                            num_entities)
    return train[pi][keep], nt[keep], nh[keep]


@functools.partial(jax.jit, static_argnames=("k",))
def _upload(ent, shared, gid, hist, agg, cnt, *, k):
    """One client's upload: its ``k`` shared rows farthest in angle from
    what it last sent, added to the server's sums."""
    emb = ent[shared]
    cos = jnp.sum(emb * hist, -1) / jnp.maximum(
        jnp.linalg.norm(emb, axis=-1) * jnp.linalg.norm(hist, axis=-1), 1e-12)
    up = top_k(1.0 - cos, k)
    return (up, hist.at[up].set(emb[up]), agg.at[gid[up]].add(emb[up]),
            cnt.at[gid[up]].add(1))


@functools.partial(jax.jit, static_argnames=("k",))
def _download(ent, shared, gid, up, agg, cnt, jitter, *, k):
    """One client's download: the aggregate less its own upload, the ``k``
    rows of highest priority that others sent, merged by Eq. 4."""
    emb = ent[shared]
    a = agg[gid] - jnp.zeros_like(emb).at[up].set(emb[up])
    pri = cnt[gid] - jnp.zeros(emb.shape[0], jnp.int32).at[up].set(1)
    dn = top_k(pri.astype(jnp.float32) + jitter * 0.5, k)
    sel = pri[dn] > 0
    new = (a[dn] + emb[dn]) / (1 + pri[dn]).astype(emb.dtype)[:, None]
    rows = jnp.where(sel[:, None], new, emb[dn])
    return ent.at[shared[dn]].set(rows), jnp.sum(sel)


class Federation:
    """The reference federation: per-client unpadded state."""

    def __init__(self, clients, num_global: int, cfg: dict, seed: int,
                 dtype=jnp.float32, half_batch: bool = False,
                 sync_as_sparse: bool = False):
        self.cfg, self.dtype, self.half_batch = cfg, dtype, half_batch
        self.sync_as_sparse = sync_as_sparse
        self.t = 0
        self.clients = clients
        d, g = cfg["dim"], cfg["gamma"]
        r_n = cfg["num_relations"]
        self.params = [init_tables(seed, c, s.num_entities, r_n, d, g, dtype,
                                   cfg["method"])
                       for c, s in enumerate(clients)]
        self.params0 = [dict(p) for p in self.params]
        zeros = lambda p: {k: jnp.zeros_like(v) for k, v in p.items()}  # noqa: E731
        self.mu = [zeros(p) for p in self.params]
        self.nu = [zeros(p) for p in self.params]
        self.step = [jnp.int32(0) for _ in clients]
        owners = np.zeros(num_global, np.int64)
        for s in clients:
            owners[s.local_to_global] += 1
        # shared rows: entities two or more clients hold, in local-id order
        self.shared = [np.nonzero(owners[s.local_to_global] >= 2)[0]
                       for s in clients]
        self.gid = [s.local_to_global[sh] for s, sh in zip(clients, self.shared)]
        self.k = [min(len(sh), max(1, int(round(len(sh) * cfg["sparsity_p"]))))
                  for sh in self.shared]
        self.ns_max = max(1, max(len(sh) for sh in self.shared))
        self.hist = [p["entity"][jnp.asarray(sh)]
                     for p, sh in zip(self.params, self.shared)]
        self.hist0 = list(self.hist)
        self.num_global = num_global
        self.batch = [min(cfg["batch_size"], s.train.shape[0]) for s in clients]
        self.steps = [max(1, s.train.shape[0] // b)
                      for s, b in zip(clients, self.batch)]
        self.scan_len = cfg["local_epochs"] * max(self.steps)
        self.key = jax.random.PRNGKey(seed + 777)
        self.train = [jnp.asarray(s.train) for s in clients]
        self.train_s = 0.0  # host seconds in local training, for the report

    def batches(self, kb, c: int):
        """The client's batches for one round, drawn by the program's rule."""
        s = self.clients[c]
        keep = np.tile(np.arange(max(self.steps)), self.cfg["local_epochs"]) < self.steps[c]
        return _draw(kb, c, self.train[c], jnp.asarray(np.nonzero(keep)[0]),
                     shape=(self.scan_len, max(self.batch)),
                     negatives=self.cfg["num_negatives"],
                     num_entities=s.num_entities)

    def kind(self, t: int) -> str:
        """The ISM schedule: ``sync_interval`` sparse rounds, then a sync."""
        s = self.cfg["sync_interval"]
        sync = (t + 1) % (s + 1) == 0 and not self.sync_as_sparse
        return "sync" if sync else "sparse"

    def round(self):
        """One round of the schedule.  Returns its kind and per-client
        ``(loss, download count)`` as numpy; a sync round downloads no
        Top-K rows, so its count is None."""
        kind = self.kind(self.t)
        self.t += 1
        self.key, kb, kj = jax.random.split(self.key, 3)
        losses = self.local_training(kb)
        if kind == "sync":
            self.full_exchange()
            return kind, losses, None
        return kind, losses, self.feds_exchange(kj)

    def local_training(self, kb) -> np.ndarray:
        cfg = self.cfg
        losses = []
        t0 = time.perf_counter()
        for c in range(len(self.clients)):
            pos, nt, nh = self.batches(kb, c)
            (self.params[c], self.mu[c], self.nu[c], self.step[c],
             loss) = train_client(
                self.params[c], self.mu[c], self.nu[c], self.step[c], pos, nt,
                nh, method=cfg["method"], gamma=cfg["gamma"], lr=cfg["lr"],
                temp=cfg["adversarial_temperature"],
                half_batch=self.half_batch,
            )
            losses.append(float(loss))
        self.train_s += time.perf_counter() - t0
        return np.asarray(losses)

    def feds_exchange(self, kj) -> np.ndarray:
        """One FedS round; returns each client's download count."""
        cfg = self.cfg
        # Eq. 1-2: each client uploads its K most changed shared rows
        agg = jnp.zeros((self.num_global, cfg["dim"]), self.dtype)
        cnt = jnp.zeros((self.num_global,), jnp.int32)
        ups = []
        for c in range(len(self.clients)):
            sh, g = jnp.asarray(self.shared[c]), jnp.asarray(self.gid[c])
            up, self.hist[c], agg, cnt = _upload(
                self.params[c]["entity"], sh, g, self.hist[c], agg, cnt,
                k=self.k[c])
            ups.append(up)
        # Eq. 3 personalised aggregate, downstream Top-K by priority, Eq. 4
        downs = []
        for c, up in enumerate(ups):
            sh, g = jnp.asarray(self.shared[c]), jnp.asarray(self.gid[c])
            jitter = jax.random.uniform(jax.random.fold_in(kj, c), (self.ns_max,))
            self.params[c]["entity"], n = _download(
                self.params[c]["entity"], sh, g, up, agg, cnt,
                jitter[: len(self.shared[c])], k=self.k[c])
            downs.append(int(n))
        return np.asarray(downs)

    def full_exchange(self) -> None:
        """The sync round's full exchange: every client sends all its shared
        rows, which become its upload history, and every shared row is
        replaced by the mean of the rows its holders sent."""
        agg = jnp.zeros((self.num_global, self.cfg["dim"]), self.dtype)
        cnt = jnp.zeros((self.num_global,), jnp.int32)
        for c in range(len(self.clients)):
            emb = self.params[c]["entity"][jnp.asarray(self.shared[c])]
            self.hist[c] = emb
            agg = agg.at[jnp.asarray(self.gid[c])].add(emb)
            cnt = cnt.at[jnp.asarray(self.gid[c])].add(1)
        for c in range(len(self.clients)):
            g = jnp.asarray(self.gid[c])
            mean = agg[g] / cnt[g].astype(self.dtype)[:, None]
            self.params[c]["entity"] = self.params[c]["entity"].at[
                jnp.asarray(self.shared[c])].set(mean)


# -------------------------------------------------------------- ranking
@functools.partial(jax.jit, static_argnames=("method", "gamma"))
def _beats(ent, rel, tri, *, method, gamma):
    """Per row of ``tri`` ``(blocks, Bk, 3)``: the gold score and how many
    candidates other than the gold one outscore it, tail and head legs."""
    score = METHODS[method].score
    cand = jnp.arange(ent.shape[0])

    def block(x):
        h, r, t = ent[x[:, 0]], rel[x[:, 1]], ent[x[:, 2]]
        gold = score(h, r, t, gamma)
        st = score(h[:, None], r[:, None], ent[None], gamma)
        sh = score(ent[None], r[:, None], t[:, None], gamma)
        bt = jnp.sum((st > gold[:, None]) & (cand != x[:, 2:3]), axis=1)
        bh = jnp.sum((sh > gold[:, None]) & (cand != x[:, 0:1]), axis=1)
        return gold, bt, bh

    return jax.lax.map(block, tri)


@functools.partial(jax.jit, static_argnames=("method", "gamma"))
def _pair_scores(ent, rel, h, r, t, *, method, gamma):
    return METHODS[method].score(ent[h], rel[r], ent[t], gamma)


def pair_block(pairs: int, dim: int, budget: int = BUDGET) -> int:
    """Filtered pairs scored at once: all of them where one side's
    ``(pairs, D)`` float32 rows fit ``budget``; else as many as both sides'
    rows fit it together (at least 1)."""
    if pairs * dim * 4 <= budget:
        return max(1, pairs)
    return max(1, budget // (2 * dim * 4))


def rank_block(num_entities: int, dim: int, budget: int = BUDGET) -> int:
    """Rows ranked at once: 32, or fewer where their ``(rows, E, D)``
    float32 candidate block would be over ``budget`` (at least 1)."""
    return max(1, min(32, budget // (num_entities * dim * 4)))


def filtered_ranks(params: dict, split: np.ndarray, known: np.ndarray,
                   method: str, gamma: float, budget: int = BUDGET):
    """Filtered ranks ``(n, 2)`` (tail leg, head leg) of ``split``'s triples
    against every entity of the client, ``known`` being all its triples.

    A candidate that forms a known triple other than the gold one is not
    counted: the beats are counted over all candidates, and those of the
    known triples are taken off after.
    """
    n = split.shape[0]
    ent, rel = params["entity"], params["relation"]
    block = rank_block(*ent.shape, budget)
    pad = (-n) % block
    tri = np.concatenate([split, np.repeat(split[:1], pad, 0)]).astype(np.int32)
    gold, bt, bh = (np.asarray(x).reshape(-1)[:n] for x in _beats(
        ent, rel, jnp.asarray(tri.reshape(-1, block, 3)), method=method,
        gamma=gamma))
    bt, bh = bt.astype(np.int64), bh.astype(np.int64)
    # known triples sharing a row's (h, r) are filtered tails, (r, t) heads
    for leg, (a, b, other, out) in enumerate(((0, 1, 2, bt), (1, 2, 0, bh))):
        row_key = split[:, a].astype(np.int64) * 1_000_003 + split[:, b]
        kn_key = known[:, a].astype(np.int64) * 1_000_003 + known[:, b]
        order = np.argsort(kn_key, kind="stable")
        kn_sorted = kn_key[order]
        lo = np.searchsorted(kn_sorted, row_key, "left")
        hi = np.searchsorted(kn_sorted, row_key, "right")
        rows = np.repeat(np.arange(n), hi - lo)
        idx = order[np.concatenate([np.arange(x, y) for x, y in zip(lo, hi)])
                    if n else np.zeros(0, np.int64)]
        cand = known[idx, other]
        keep = cand != split[rows, other]
        rows, cand = rows[keep], cand[keep]
        if rows.size == 0:
            continue
        h = split[rows, 0] if leg == 0 else cand
        t = cand if leg == 0 else split[rows, 2]
        r = split[rows, 1]
        step = pair_block(rows.size, ent.shape[1], budget)
        s = np.concatenate([
            np.asarray(_pair_scores(ent, rel, jnp.asarray(h[i:i + step]),
                                    jnp.asarray(r[i:i + step]),
                                    jnp.asarray(t[i:i + step]), method=method,
                                    gamma=gamma))
            for i in range(0, rows.size, step)])
        np.subtract.at(out, rows, (s > gold[rows]).astype(np.int64))
    return np.stack([bt + 1, bh + 1], axis=1)


def metric_block(ranks: np.ndarray) -> np.ndarray:
    """``[mrr, hits@1, hits@3, hits@10, count]`` of ``(n, 2)`` ranks."""
    r = ranks.reshape(-1).astype(np.float64)
    if r.size == 0:
        return np.zeros(5)
    return np.array([np.mean(1.0 / r), np.mean(r <= 1), np.mean(r <= 3),
                     np.mean(r <= 10), ranks.shape[0]])


def evaluate(params_per_client, clients, split: str, max_triples: int,
             method: str, gamma: float) -> np.ndarray:
    """``(C, 5)`` metric block of each client's first ``max_triples``
    triples of ``split``."""
    out = []
    for p, s in zip(params_per_client, clients):
        rows = getattr(s, split)[:max_triples]
        known = np.concatenate([s.train, s.valid, s.test])
        out.append(metric_block(filtered_ranks(p, rows, known, method, gamma)))
    return np.stack(out)
