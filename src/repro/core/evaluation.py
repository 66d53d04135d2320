"""Device-resident batched filtered-ranking evaluation (the eval subsystem).

Evaluation used to be the last host-bound subsystem: every eval boundary
pulled all padded entity tables back into per-client host objects
(``CycleEngine.sync_clients``) and ranked with per-client dense ``(B, E)``
bool numpy filter masks in 256-row jitted chunks.  The paper's
convergence-speed claims (MRR/Hits@K *versus bytes transmitted*) are
measured at exactly these boundaries, so eval cost polluted every
communication-efficiency benchmark.  This module makes evaluation a
device-resident batched program over the same padded ``(C, ...)`` state
layout the engines already share:

* :class:`EvalBank` — one split's device-resident evaluation state, built
  ONCE at simulation construction: padded ``(C, B_max, 3)`` eval triple
  banks, filtered-setting masks bit-packed to ``(C, B_max, W)`` uint32
  words with ``W = ceil(E_max/32)`` (a ~32x memory cut over the per-client
  ``(B, E)`` bool masks), and per-client true row counts.
* :class:`BatchedEvaluator` — a single jitted (host) / ``shard_map`` (pod)
  program that scores every client's full candidate set at once, E-dim
  chunked via ``lax.scan`` over the scoring ops of
  :mod:`repro.kernels.ops` so the ``(C, B_max, E_max)`` score tensor is
  never materialized, applies the packed filters with bitwise ops, and
  reduces filtered ranks to a per-client ``(mrr, hits@1, hits@3, hits@10,
  count)`` block on device — the host reads back only
  ``(C, EVAL_BLOCK_COLS)`` scalars per boundary.  Under an entity-sharded
  2-D mesh (:func:`repro.launch.mesh.make_federation_mesh` with
  ``entity_devices > 1``) each shard scans only its own candidate block and
  the integer beat counts ``psum`` exactly, so ranks stay bitwise equal to
  the unsharded scan.

Exactness contract: on the default (ref) scoring dispatch the integer
filtered ranks (both head and tail legs) are **exactly equal** to the
numpy-oracle ranks of ``repro.federated.client.KGEClient.ranks`` —
candidate scores are computed with the same :mod:`repro.kge.scoring`
functions on the same rows, the gold candidate is excluded explicitly (so
a last-ulp difference in the separately computed gold score can never flip
its own comparison), and padding candidates/rows are masked.
``tests/test_evaluation.py`` property-tests rank equality over randomized
heterogeneous federations.  On TPU/interpret, candidate scores route
through the family-tagged eval kernels
(:attr:`repro.kge.scoring.ScoringSpec.family`): the distance family
(TransE/RotatE/pRotatE) through the tiled ``dist_cand_score_pallas``
VPU kernel and the bilinear family (ComplEx/DistMult) through the
matmul-style ``bilinear_cand_score_pallas`` MXU kernel.  Both are
tolerance-tested (~1e-4) rather than bitwise against the scoring
functions — a near-tie candidate within that tolerance of the gold
score may shift its integer rank by one there.

The bit-packed filter builders (:func:`build_known_index`,
:func:`pack_filter_rows`, :func:`unpack_filter_words`) are shared with the
host oracle, so ``KGEClient`` no longer holds dense bool masks either.

:class:`repro.core.state.SuperstepEngine` composes
:attr:`BatchedEvaluator.eval_core` into its scanned plans as ``"eval"``
segments (:data:`repro.core.sync.PLAN_KINDS`), so a whole ISM span
*including its eval round* compiles into one program with zero
intermediate ``sync_clients`` host round-trips.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import eshard, telemetry
from repro.core.telemetry import scope
from repro.data.partition import ClientData
from repro.kernels import ops as kernel_ops
from repro.kge import scoring as kge_scoring

#: Bits per packed filter word.
WORD_BITS = 32

#: Hits@K cutoffs in the metric block, lowest first.  The paper's protocol
#: reports Hits@10; @1/@3 ride along in the same on-device reduction.
HITS_LEVELS = (1, 3, 10)

#: Hits@K cutoff used by the paper's headline protocol.
HITS_AT = 10

#: Columns of the per-client metric block: [mrr, hits@1, hits@3, hits@10,
#: count] — see :func:`repro.federated.metrics.aggregate_eval_block`.
EVAL_BLOCK_COLS = 2 + len(HITS_LEVELS)


# ------------------------------------------------------------- filter packing
def build_known_index(*triple_arrays: np.ndarray) -> dict:
    """Filtered-setting lookup over all known triples.

    Maps ``("t", h, r) -> {tails}`` and ``("h", r, t) -> {heads}`` — the
    standard KGE filtered protocol index, shared by the host oracle
    (``KGEClient``) and the packed-bank builders here.
    """
    known: dict = {}
    for arr in triple_arrays:
        for h, r, t in np.asarray(arr).tolist():
            known.setdefault(("t", h, r), set()).add(t)
            known.setdefault(("h", r, t), set()).add(h)
    return known


def num_filter_words(num_entities: int) -> int:
    """``W = ceil(E / 32)`` packed words per eval row (at least 1)."""
    return max(1, (int(num_entities) + WORD_BITS - 1) // WORD_BITS)


def pack_filter_rows(
    triples: np.ndarray,  # (B, 3) local-id eval triples
    known: dict,  # build_known_index output
    num_words: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Bit-packed filtered-setting masks for a block of eval triples.

    Returns ``(ft_words, fh_words)``, each ``(B, num_words)`` uint32; bit
    ``e`` of row ``i`` is set iff entity ``e`` is a known tail (resp. head)
    for triple ``i`` *other than the gold answer itself* — exactly the mask
    the oracle used to hold as a dense ``(B, E)`` bool array.
    """
    b = int(triples.shape[0])
    ft = np.zeros((b, num_words), np.uint32)
    fh = np.zeros((b, num_words), np.uint32)
    for i, (h, r, t) in enumerate(np.asarray(triples).tolist()):
        for e in known.get(("t", h, r), ()):
            if e != t:
                ft[i, e >> 5] |= np.uint32(1 << (e & 31))
        for e in known.get(("h", r, t), ()):
            if e != h:
                fh[i, e >> 5] |= np.uint32(1 << (e & 31))
    return ft, fh


def unpack_filter_words(words: jnp.ndarray, num_entities: int) -> jnp.ndarray:
    """(B, W) packed words -> (B, num_entities) bool mask (jit-safe).

    The host oracle's ``_rank_batch`` unpacks on device, so packed words are
    the only resident representation anywhere.
    """
    e = jnp.arange(num_entities, dtype=jnp.int32)
    bits = words[:, e >> 5] >> (e & 31).astype(jnp.uint32)
    return (bits & 1).astype(bool)


# ------------------------------------------------------------------ the bank
class EvalBank(NamedTuple):
    """One split's device-resident eval state; every leaf leads with the
    client axis, so one ``PartitionSpec('clients')`` shards the bundle."""

    triples: jnp.ndarray  # (C, B_max, 3) int32, zero-padded rows
    count: jnp.ndarray  # (C,) int32 true eval-triple counts
    ft_words: jnp.ndarray  # (C, B_max, W) uint32 packed tail filters
    fh_words: jnp.ndarray  # (C, B_max, W) uint32 packed head filters
    num_ent: jnp.ndarray  # (C,) int32 local entity counts (candidate bound)


def build_eval_bank(
    datas: Sequence[ClientData],
    split: str,
    max_triples: int,
    e_max: int,
    known: Optional[Sequence[dict]] = None,
    num_words: Optional[int] = None,
) -> EvalBank:
    """Pad one split's eval triples + packed filters across the federation.

    ``known`` may pass pre-built per-client :func:`build_known_index` dicts
    (e.g. shared with ``KGEClient``); otherwise they are built here from
    each client's train/valid/test.  ``num_words`` may widen the word axis
    beyond ``ceil(e_max/32)`` (the evaluator sizes it to the padded
    candidate range so chunk word-slices never run off the end).
    """
    c_n = len(datas)
    w = num_words if num_words is not None else num_filter_words(e_max)
    caps = [min(int(getattr(d, split).shape[0]), int(max_triples)) for d in datas]
    b_max = max(1, max(caps, default=0))
    triples = np.zeros((c_n, b_max, 3), np.int32)
    ft = np.zeros((c_n, b_max, w), np.uint32)
    fh = np.zeros((c_n, b_max, w), np.uint32)
    for c, d in enumerate(datas):
        n = caps[c]
        if n == 0:
            continue
        tri = np.asarray(getattr(d, split))[:n]
        triples[c, :n] = tri
        kn = known[c] if known is not None else build_known_index(
            d.train, d.valid, d.test
        )
        ft[c, :n], fh[c, :n] = pack_filter_rows(tri, kn, w)
    return EvalBank(
        triples=jnp.asarray(triples),
        count=jnp.asarray(np.asarray(caps, np.int32)),
        ft_words=jnp.asarray(ft),
        fh_words=jnp.asarray(fh),
        num_ent=jnp.asarray(
            np.asarray([d.num_entities for d in datas], np.int32)
        ),
    )


# ----------------------------------------------------------------- evaluator
class BatchedEvaluator:
    """Compiled filtered-ranking evaluation over padded federation params.

    Built once per federation; owns one :class:`EvalBank` per split and the
    compiled metric programs.  ``mesh=None`` compiles a single-device jit;
    with a 1-D client mesh the same core runs under ``shard_map`` (the
    reduction is fully per-client, so no collective is needed).

    ``eval_core(params, bank) -> (C, EVAL_BLOCK_COLS)`` is the pure program
    body — the
    :class:`repro.core.state.SuperstepEngine` inlines it as the ``"eval"``
    plan segment of a scanned superstep, which is what makes "one host
    dispatch per superstep" true through eval boundaries.
    """

    def __init__(
        self,
        datas: Sequence[ClientData],
        *,
        method: str,
        gamma: float,
        e_max: int,
        max_triples: int = 2000,
        splits: Sequence[str] = ("valid", "test"),
        chunk: int = 512,
        known: Optional[Sequence[dict]] = None,
        mesh=None,
        axis_name: str = "clients",
        entity_axis: Optional[str] = None,
    ):
        # fail fast with the registry's self-describing error rather than at
        # first compiled eval dispatch
        kge_scoring.get_scoring(method)
        self.method = method
        self.gamma = float(gamma)
        self.e_max = int(e_max)
        if max(int(d.num_entities) for d in datas) > self.e_max:
            raise ValueError(
                "e_max smaller than the largest client entity count; the "
                "bank's packed filter words would truncate"
            )
        # candidate chunk: scores live as (C, B_max, chunk) tiles inside the
        # scan, never (C, B_max, E_max).  Rounded to whole 32-bit filter
        # words so each scan step slices the chunk's packed words once and
        # expands bits in-register, instead of gathering one word per
        # candidate (32x the bandwidth of the packed representation).
        chunk = max(1, min(int(chunk), self.e_max))
        self.chunk = -(-chunk // WORD_BITS) * WORD_BITS
        self._eaxis = entity_axis if mesh is not None else None
        if self._eaxis is not None and self._eaxis not in dict(mesh.shape):
            raise ValueError(
                f"entity_axis {self._eaxis!r} not in mesh axes {tuple(mesh.shape)}"
            )
        n_e = int(dict(mesh.shape)[self._eaxis]) if self._eaxis else 1
        self.n_eshards = n_e
        if self._eaxis is not None:
            # entity-sharded: the candidate axis must mirror the engine's
            # padded state layout (pad_rows(e_max, n_e, 32)) so the entity
            # table blocks AND the packed filter word axis split evenly;
            # each shard then scans its own block span, chunk-padded
            # locally, and the integer beat counts psum exactly.
            self.e_pad = eshard.pad_rows(self.e_max, n_e, WORD_BITS)
        else:
            self.e_pad = -(-self.e_max // self.chunk) * self.chunk
        self.banks: Dict[str, EvalBank] = {
            s: build_eval_bank(datas, s, max_triples, self.e_max, known=known,
                               num_words=self.e_pad // WORD_BITS)
            for s in splits
        }
        # work counters of the ``eval`` telemetry span, per split: candidate
        # scores an evaluate call computes (both legs, rows and candidates
        # padded as the program runs them), those of them the kernels
        # reduce on the MXU, and those of real triples against their
        # client's real entities
        if self._eaxis is None:
            scan_w = self.e_pad
        else:  # each shard scans its own chunk-padded block
            scan_w = n_e * (-(-(self.e_pad // n_e) // self.chunk) * self.chunk)
        n_ent = [int(d.num_entities) for d in datas]
        mxu_legs = kernel_ops.cand_legs_on_mxu(method)
        self._eval_work = {
            s: {
                "scores": len(datas) * b.triples.shape[1] * 2 * scan_w,
                "scores_mxu": len(datas) * b.triples.shape[1] * mxu_legs
                * scan_w,
                "scores_real": sum(
                    2 * int(n) * e for n, e in zip(np.asarray(b.count), n_ent)
                ),
            }
            for s, b in self.banks.items()
        }
        self.eval_core = self._make_eval_core()
        self._rank_core = self._make_rank_core()
        if mesh is None:
            self._eval = jax.jit(self.eval_core)
            self._ranks = jax.jit(self._rank_core)
        else:
            p = jax.sharding.PartitionSpec(axis_name)
            pp = self._params_spec(axis_name)
            pb = self._bank_spec(axis_name)
            self._eval = jax.jit(jax.shard_map(
                self.eval_core, mesh=mesh, in_specs=(pp, pb), out_specs=p,
            ))
            self._ranks = jax.jit(jax.shard_map(
                self._rank_core, mesh=mesh, in_specs=(pp, pb), out_specs=(p, p),
            ))

    # --------------------------------------------------------------- specs
    def _params_spec(self, axis_name: str):
        """PartitionSpec pytree for the padded params dict under the mesh."""
        p = jax.sharding.PartitionSpec(axis_name)
        if self._eaxis is None:
            return p
        return {
            "entity": jax.sharding.PartitionSpec(axis_name, self._eaxis),
            "relation": p,
        }

    def _bank_spec(self, axis_name: str):
        """:class:`EvalBank` spec — filter words shard on the word axis."""
        p = jax.sharding.PartitionSpec(axis_name)
        if self._eaxis is None:
            return p
        pw = jax.sharding.PartitionSpec(axis_name, None, self._eaxis)
        return EvalBank(triples=p, count=p, ft_words=pw, fh_words=pw, num_ent=p)

    # ------------------------------------------------------- program bodies
    def _make_rank_core(self):
        method, gamma = self.method, self.gamma
        chunk, e_pad = self.chunk, self.e_pad
        eaxis = self._eaxis

        @scope("feds/eval")
        def rank_core(params, bank: EvalBank):
            """Filtered ranks ``(rank_t, rank_h)``, each (C, B_max) int32.

            Entity-sharded (``eaxis`` set): ``params['entity']`` and the
            bank's packed filter words arrive as per-shard blocks; each
            shard scans its own chunk-padded candidate span with global
            candidate ids ``base + local``, masks candidates past its block
            edge, and the integer beat counts ``psum`` exactly — rank
            output is bitwise identical to the unsharded scan because only
            whole-boolean counts cross the shard boundary.
            """
            ent = params["entity"]  # (C, E_blk, D) block (full when unsharded)
            c_n, e_blk, _d = ent.shape
            if eaxis is None:
                span, base = e_pad, 0
            else:
                span = -(-e_blk // chunk) * chunk
                base = eshard.shard_offset(eaxis, e_blk)
            with scope("feds/eval/filter"):
                ent_p = jnp.pad(ent, ((0, 0), (0, span - e_blk), (0, 0)))
                ftw, fhw = bank.ft_words, bank.fh_words
                if span > e_blk and eaxis is not None:
                    pw = ((0, 0), (0, 0), (0, (span - e_blk) // WORD_BITS))
                    ftw, fhw = jnp.pad(ftw, pw), jnp.pad(fhw, pw)
            tri = bank.triples
            h, r, t = tri[..., 0], tri[..., 1], tri[..., 2]
            h_e = eshard.dist_take_rows(ent, h, eaxis)  # (C, B, D)
            t_e = eshard.dist_take_rows(ent, t, eaxis)
            r_e = jnp.take_along_axis(params["relation"], r[:, :, None], axis=1)
            # the gold triple's score — shared by both legs; the gold
            # CANDIDATE is excluded from the counts below, so rank equality
            # with the oracle never hinges on this value's last ulp
            gold = kernel_ops.kge_score_rows(h_e, r_e, t_e, method, gamma)
            # the counts vary over every mesh axis the tables and triples do
            zero = eshard.vary_like(jnp.zeros(h.shape, jnp.int32), ent, tri)
            c_b = h.shape[:2]
            n_words = chunk // WORD_BITS  # chunk is a whole-word multiple
            bit = jnp.arange(WORD_BITS, dtype=jnp.uint32)

            @scope("feds/eval/filter")
            def unpack_chunk(words, w0):
                """Slice the chunk's packed words ONCE and expand bits
                in-register: (C, B, W) -> (C, B, chunk) 0/1."""
                wc = jax.lax.dynamic_slice_in_dim(words, w0, n_words, axis=2)
                return ((wc[..., None] >> bit) & 1).reshape(c_b + (chunk,))

            def step(carry, e0):
                cnt_t, cnt_h = carry
                cand_loc = e0 + jnp.arange(chunk, dtype=jnp.int32)  # (Ec,)
                cand = base + cand_loc  # global candidate ids
                with scope("feds/eval/score"):
                    ce = jax.lax.dynamic_slice_in_dim(ent_p, e0, chunk, axis=1)
                    # both legs' candidate scores, (C, B, Ec) tiles
                    ts, hs = kernel_ops.kge_cand_scores(
                        h_e, r_e, t_e, ce, method, gamma
                    )
                w0 = e0 // WORD_BITS
                fb_t = unpack_chunk(ftw, w0)
                fb_h = unpack_chunk(fhw, w0)
                with scope("feds/eval/rank"):
                    ok = cand[None, :] < bank.num_ent[:, None]  # (C, Ec)
                    if eaxis is not None:
                        # span-padding candidates would alias the NEXT
                        # shard's global ids — mask past this shard's block
                        # edge
                        ok = ok & (cand_loc[None, :] < e_blk)
                    beat_t = (
                        (ts > gold[:, :, None])
                        & (fb_t == 0)
                        & ok[:, None, :]
                        & (cand[None, None, :] != t[:, :, None])
                    )
                    beat_h = (
                        (hs > gold[:, :, None])
                        & (fb_h == 0)
                        & ok[:, None, :]
                        & (cand[None, None, :] != h[:, :, None])
                    )
                    return (
                        cnt_t + beat_t.sum(-1).astype(jnp.int32),
                        cnt_h + beat_h.sum(-1).astype(jnp.int32),
                    ), None

            (cnt_t, cnt_h), _ = jax.lax.scan(
                step, (zero, zero),
                jnp.arange(0, span, chunk, dtype=jnp.int32),
            )
            with scope("feds/eval/rank"):
                if eaxis is not None:
                    cnt_t = jax.lax.psum(cnt_t, eaxis)
                    cnt_h = jax.lax.psum(cnt_h, eaxis)
                return cnt_t + 1, cnt_h + 1

        return rank_core

    def _make_eval_core(self):
        rank_core = self._make_rank_core()

        def eval_core(params, bank: EvalBank):
            """(C, 5) per-client ``[mrr, hits@1, hits@3, hits@10, count]``
            scalar block (column order fixed by :data:`HITS_LEVELS`)."""
            rank_t, rank_h = rank_core(params, bank)
            return metric_block(rank_t, rank_h, bank)

        @scope("feds/eval/rank")
        def metric_block(rank_t, rank_h, bank: EvalBank):
            b_max = rank_t.shape[1]
            valid = jnp.arange(b_max)[None, :] < bank.count[:, None]
            rt = rank_t.astype(jnp.float32)
            rh = rank_h.astype(jnp.float32)
            recip = jnp.where(valid, 1.0 / rt + 1.0 / rh, 0.0).sum(axis=1)
            hits = [
                jnp.where(
                    valid,
                    (rank_t <= lvl).astype(jnp.float32)
                    + (rank_h <= lvl).astype(jnp.float32),
                    0.0,
                ).sum(axis=1)
                for lvl in HITS_LEVELS
            ]
            denom = jnp.maximum(2.0 * bank.count.astype(jnp.float32), 1.0)
            return jnp.stack(
                [recip / denom]
                + [h / denom for h in hits]
                + [bank.count.astype(jnp.float32)],
                axis=1,
            )

        return eval_core

    # --------------------------------------------------------------- driving
    def evaluate(self, params: dict, split: str) -> np.ndarray:
        """Run the compiled program; returns the (C, EVAL_BLOCK_COLS) block
        as numpy — the ONLY host transfer an eval boundary performs."""
        with telemetry.span("eval", split=split, **self._eval_work[split]):
            return np.asarray(self._eval(params, self.banks[split]))

    def ranks(self, params: dict, split: str) -> tuple[np.ndarray, np.ndarray]:
        """Integer filtered ranks (tail leg, head leg), each (C, B_max) —
        padded rows carry garbage; mask with ``bank.count``.  Test/debug
        path: production reads only the block of :meth:`evaluate`."""
        rt, rh = self._ranks(params, self.banks[split])
        return np.asarray(rt), np.asarray(rh)
