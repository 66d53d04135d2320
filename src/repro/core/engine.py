"""Unified FedS round engine: one jitted program over batched client state.

This module is the single implementation of the paper's communication round
(upstream entity-wise Top-K -> personalized aggregation Eq. 3 -> downstream
Top-K -> Eq. 4 apply) that both deployment shapes share:

* **host** — all clients' shared-entity rows are stacked into padded
  ``(C, Ns_max, D)`` buffers with validity masks and the whole round runs as
  one ``jax.jit`` program on a single device (the federated simulation path),
* **pod**  — the same per-shard function runs under ``shard_map`` over the
  client axis of a mesh; the only cross-client exchange is ONE ``all_gather``
  of the fixed-size ``(k_max,)`` index / ``(k_max, D)`` value / mask buffers
  (EXPERIMENTS.md §Perf: the server round-trip is computed redundantly
  on-shard, which is free once the gather delivered the inputs).

Heterogeneity (clients with different shared-entity counts and different
``K``) is expressed with static shapes: rows are padded to ``Ns_max`` and
masked by ``valid``; Top-K always selects ``k_max`` slots and masks slots
``>= k_c`` per client.  Change scoring runs through the fused Pallas kernel
across the flattened client axis — one kernel launch for all clients.

Semantic deltas vs the numpy reference (:mod:`repro.core.aggregate`), as
already documented for :mod:`repro.core.distributed`: static K and a
deterministic jitter tie-break instead of random tie-breaking.  The host
reference path stays available as ``engine="reference"`` in the simulation
and is what the property tests compare against.

Wire payloads go through a pluggable :class:`repro.core.codecs.WireCodec`
(registry in :mod:`repro.core.codecs`: identity / int8 / lowrank /
topk-dims), applied inside the jitted round; error-feedback codecs
additionally thread a ``(C, Ns_max, D)`` residual buffer through
:func:`batched_sparse_round` (carried in
:class:`repro.core.state.FederationState` by the cycle engines).

ISM round-schedule semantics: this module implements the two round *kinds* —
:func:`batched_sparse_round` (entity-wise Top-K, the ``"sparse"`` kind) and
:func:`batched_sync_round` (full FedE mean, the ``"sync"`` kind) — but does
NOT decide when each runs.  The schedule (``s`` sparse rounds then one sync
round per period) lives in :mod:`repro.core.sync` (:func:`~repro.core.sync.
round_kind`); :class:`repro.core.state.CycleEngine` fuses one scheduled
round with its local training, and :class:`repro.core.state.SuperstepEngine`
scans whole schedule spans into single programs.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import eshard
from repro.core.telemetry import (
    RoundTelemetry,
    nonfinite_count,
    record_spec as telemetry_record_spec,
    residual_mass,
    scope,
    score_histogram,
    shared_divergence,
    update_norm,
    upload_overlap,
)
from repro.core.codecs import IdentityCodec, WireCodec
from repro.core.sparsify import change_scores, sparsity_k, top_k_select
from repro.kernels import ops as kernel_ops

# --------------------------------------------------------------------------
def make_client_mesh(num_devices: int, axis_name: str = "clients"):
    """A 1-D mesh over ``num_devices`` for the client axis."""
    return jax.make_mesh((num_devices,), (axis_name,),
                         axis_types=(jax.sharding.AxisType.Auto,))


# --------------------------------------------------------------------------
# shared primitives (also used by repro.core.distributed)
def segment_aggregate(
    ids: jnp.ndarray,  # (M,) int segment ids
    vals: jnp.ndarray,  # (M, D) contribution rows (already masked/weighted)
    weights: jnp.ndarray,  # (M,) contribution counts (0 for masked slots)
    num_segments: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Eq. 3 scatter-add: dense (S, D) aggregate + (S,) priority counts."""
    agg = jax.ops.segment_sum(vals, ids, num_segments=num_segments)
    pri = jax.ops.segment_sum(weights, ids, num_segments=num_segments)
    return agg, pri


def client_ordered_aggregate(
    ids: jnp.ndarray,  # (C_local, M) segment ids of this shard's clients
    vals: jnp.ndarray,  # (C_local, M, D)
    weights: jnp.ndarray,  # (C_local, M)
    num_segments: int,
    axis_name: str,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`segment_aggregate` over every client of the ``axis_name``
    mesh axis, added in client order as on one device.

    Shards take turns: shard ``j`` adds its clients' segment sums, one
    client at a time, onto the running total, and hands the total to every
    shard bit for bit (:func:`repro.core.eshard.replicated`).  Each shard
    holds ``(S, D)`` buffers, as a ``psum`` of partial sums would, but the
    additions keep the unsharded order — a float ``psum`` would reorder
    them once a shard holds several clients.
    """
    def add_client(acc, x):
        tot, cnt = segment_aggregate(*x, num_segments)
        return (acc[0] + tot, acc[1] + cnt), None

    acc = (
        jnp.zeros((num_segments, vals.shape[-1]), vals.dtype),
        jnp.zeros((num_segments,), weights.dtype),
    )
    for src in range(jax.lax.axis_size(axis_name)):
        acc = tuple(eshard.vary_like(a, vals, weights) for a in acc)
        acc, _ = jax.lax.scan(add_client, acc, (ids, vals, weights))
        acc = eshard.replicated(acc, axis_name, src=src)
    return acc


def downstream_sign(
    pri: jnp.ndarray,  # (N,) priority counts
    rank_key: jnp.ndarray,  # (N,) priority + tie-break jitter
    k: int,
) -> jnp.ndarray:
    """Static downstream Top-K selection as a (N,) int8 sign vector.

    Rows with zero priority are never selected (the paper's "fewer than K
    available" rule).
    """
    sel = top_k_select(rank_key, k)
    sign = jnp.zeros(pri.shape[0], jnp.int8).at[sel].set(1)
    return jnp.where(pri > 0, sign, 0)


# --------------------------------------------------------------------------
def build_padded_views(views: Sequence, num_global: int, sparsity_p: float):
    """Static padded buffers shared by RoundEngine and the fused CycleEngine.

    Returns ``(gid, valid, k_per_client, ns_max, k_max)`` as numpy arrays /
    ints; ``gid`` padding slots hold ``num_global`` (the round functions treat
    it as a throwaway aggregation segment).
    """
    ns = [v.num_shared for v in views]
    ns_max = max(1, max(ns, default=0))
    k_per_client = np.asarray([sparsity_k(n, sparsity_p) for n in ns], np.int32)
    k_max = max(1, int(k_per_client.max(initial=0)))
    gid = np.full((len(views), ns_max), num_global, np.int32)
    valid = np.zeros((len(views), ns_max), bool)
    for c, v in enumerate(views):
        gid[c, : v.num_shared] = v.shared_global
        valid[c, : v.num_shared] = True
    return gid, valid, k_per_client, ns_max, k_max


# --------------------------------------------------------------------------
# the batched round (runs plain-jit on host, or per-shard under shard_map)
def batched_sparse_round(
    emb: jnp.ndarray,  # (C_local, Ns_max, D) shared-entity rows
    hist: jnp.ndarray,  # (C_local, Ns_max, D) upload history
    gid: jnp.ndarray,  # (C_local, Ns_max) global entity id; padding -> num_global
    valid: jnp.ndarray,  # (C_local, Ns_max) bool row validity
    k: jnp.ndarray,  # (C_local,) per-client K
    jitter: jnp.ndarray,  # (C_local, Ns_max) tie-break noise in [0, 1)
    *,
    k_max: int,
    num_global: int,
    codec: WireCodec,
    axis_name: Optional[str],
    res: Optional[jnp.ndarray] = None,  # (C_local, Ns_max, D) EF residuals
    entity_axis: Optional[str] = None,
    faults=None,  # Optional[repro.core.faults.RoundFaults] of (C_local,) masks
    straggler: Optional[jnp.ndarray] = None,  # (C_local,) f32 straggler set
    queue=None,  # (q_idx, q_val, q_msk) straggler in-flight message buffers
    prev=None,  # (prev_idx, prev_msk) telemetry carry (core/telemetry.py)
):
    """One sparse FedS round over padded batched client state.

    Returns ``(emb', hist', down_count)``, plus ``res'`` when ``res`` is
    given, plus the advanced ``queue`` when ``queue`` is given, plus
    ``(RoundTelemetry, (prev_idx', prev_msk'))`` appended last when ``prev``
    is given (the flight-recorder record and the advanced overlap carry;
    ``prev=None`` compiles exactly the untelemetered program).  With an
    error-feedback codec (``codec.has_residual``) the residual of each
    *uploaded* row — what the codec's lossy round-trip dropped — is banked
    in ``res`` and re-injected into that row's wire value the next time it
    is selected; rows not uploaded this round keep their banked residual
    untouched.  Non-residual codecs pass ``res`` through unchanged.

    With ``faults`` (:class:`repro.core.faults.RoundFaults`), participation
    gates what is *computed* (history/residual refresh, download selection),
    ``part * up_ok`` gates what is *delivered* into the Eq. 3 aggregate, and
    ``part * dn_ok`` gates whether the Eq. 4 apply lands.  A dropped upload
    still refreshed the sender's history and residual bank — the client
    cannot know the message was lost.  ``faults=None`` compiles exactly the
    fault-free program.

    With ``queue`` (plus the static ``straggler`` indicator), clients in the
    straggler set contribute the message at the HEAD of their fixed-depth
    queue to this round's aggregate — the upload they computed ``lag``
    sparse rounds ago — while this round's freshly-computed (and
    delivery-masked) message is pushed at the tail.  Non-straggler pushes
    are masked to zero, so their queues stay empty.  Eq. 3's
    own-contribution subtraction and priority discount are built from the
    *contributed* message, history/residual refresh from the *fresh* one.

    With ``entity_axis`` the ``(..., D)`` row buffers (``emb``, ``hist``,
    ``res``) are this shard's ``(C, Ns_pad / n_shards, D)`` blocks of a
    row-sharded slot axis, while the cheap per-slot vectors (``gid``,
    ``valid``, ``jitter``) stay replicated at full ``(C, Ns_pad)`` width.
    Change scoring and the Eq. 4 apply run on the local block only; the two
    Top-K selections become per-shard ``top_k`` + one ``(K, score)``
    candidate merge (:func:`repro.core.sparsify.top_k_select`); the Eq. 3
    segment-sum runs redundantly per shard on the replicated merged uploads
    so its f32 summation order — hence the result, bit for bit — matches
    the unsharded round.
    """
    if codec.has_residual and res is None:
        raise ValueError(
            f"codec {codec!r} carries error-feedback residual state; "
            "pass the (C, Ns_max, D) res buffer (CycleEngine/SuperstepEngine "
            "thread it through FederationState)"
        )
    if queue is not None and straggler is None:
        raise ValueError("straggler indicator required with a message queue")
    ea = entity_axis
    cl, ns_blk, d = emb.shape  # ns_blk == full Ns_max when unsharded
    gid_blk = eshard.local_block(gid, ea, ns_blk)
    valid_blk = eshard.local_block(valid, ea, ns_blk)
    jitter_blk = eshard.local_block(jitter, ea, ns_blk)
    validf = valid_blk.astype(emb.dtype)
    slot = jnp.arange(k_max)[None, :]

    with scope("feds/comm/upload"):
        # -- upstream Top-K (Eq. 1-2): one fused kernel call across all clients
        scores = change_scores(
            emb.reshape(cl * ns_blk, d), hist.reshape(cl * ns_blk, d)
        ).reshape(cl, ns_blk)
        scores = jnp.where(valid_blk, scores, -jnp.inf)
        up_idx = top_k_select(scores, k_max, entity_axis=ea)  # (cl, k_max) global
        up_mask = (slot < k[:, None]) & jnp.take_along_axis(valid, up_idx, axis=1)
        up_maskf = up_mask.astype(emb.dtype)

        # (cl, ns_blk) 0/1 — which of my local rows went upstream this round;
        # under faults only participating clients compute an upload at all
        sent_maskf = up_maskf if faults is None else up_maskf * faults.part[:, None]
        uploaded = eshard.scatter_add_vec(
            jnp.zeros((cl, ns_blk), emb.dtype), up_idx, sent_maskf, ea
        )
        new_hist = jnp.where(uploaded[:, :, None] > 0, emb, hist)

        vals = eshard.dist_take_rows(emb, up_idx, ea)  # (cl, k_max, d)
        if codec.has_residual:
            # error feedback: re-inject the banked residual before encoding, bank
            # the fresh encode error after.  Only rows a participating client
            # actually encoded refresh the bank — a dropped-in-flight upload
            # still banked its error (the sender cannot know), an absent client
            # banked nothing.
            res_sel = eshard.dist_take_rows(res, up_idx, ea)
            corrected = vals + res_sel * up_maskf[:, :, None]
            vals = codec.roundtrip(corrected.reshape(-1, d)).reshape(cl, k_max, d)
            err_rows = (corrected - vals) * sent_maskf[:, :, None]
            err_full = eshard.scatter_add_rows(
                jnp.zeros((cl, ns_blk, d), emb.dtype), up_idx, err_rows, ea
            )
            new_res = jnp.where(uploaded[:, :, None] > 0, err_full, res)
        else:
            vals = codec.roundtrip(vals.reshape(-1, d)).reshape(cl, k_max, d)
            new_res = res

    with scope("feds/comm/aggregate"):
        # the message CONTRIBUTED to this round's Eq. 3 aggregate: normally the
        # fresh wire-coded upload (delivery-masked under faults); stragglers
        # contribute the head of their in-flight queue — the message they sent
        # ``lag`` sparse rounds ago — while the fresh message is pushed at the
        # tail (masked to zero for non-stragglers, whose queues stay empty)
        if faults is None:
            msg_maskf = up_maskf
        else:
            msg_maskf = up_maskf * (faults.part * faults.up_ok)[:, None]
        if queue is not None:
            q_idx, q_val, q_msk = queue
            stragb = straggler[:, None] > 0.5
            contrib_idx = jnp.where(stragb, q_idx[:, 0], up_idx)
            contrib_val = jnp.where(stragb[:, :, None], q_val[:, 0], vals)
            contrib_msk = jnp.where(stragb, q_msk[:, 0], msg_maskf)
            new_queue = (
                jnp.concatenate([q_idx[:, 1:], up_idx[:, None]], axis=1),
                jnp.concatenate([q_val[:, 1:], vals[:, None]], axis=1),
                jnp.concatenate(
                    [q_msk[:, 1:], (msg_maskf * straggler[:, None])[:, None]],
                    axis=1,
                ),
            )
        else:
            contrib_idx, contrib_val, contrib_msk = up_idx, vals, msg_maskf
            new_queue = None

        # this client's wire-coded contribution scattered back to row positions,
        # for the Eq. 3 own-contribution subtraction below
        own_wire = eshard.scatter_add_rows(
            jnp.zeros((cl, ns_blk, d), emb.dtype), contrib_idx,
            contrib_val * contrib_msk[:, :, None], ea,
        )
        if faults is None and queue is None:
            uploaded_contrib = uploaded
        else:
            uploaded_contrib = eshard.scatter_add_vec(
                jnp.zeros((cl, ns_blk), emb.dtype), contrib_idx, contrib_msk, ea
            )

        # -- exchange: one all-gather of fixed-size buffers (no-op on host)
        if faults is None and queue is None:
            up_gid = jnp.where(
                up_mask, jnp.take_along_axis(gid, up_idx, axis=1), num_global
            )
        else:
            up_gid = jnp.where(
                contrib_msk > 0,
                jnp.take_along_axis(gid, contrib_idx, axis=1), num_global,
            )
        ex_vals, ex_msk = contrib_val, contrib_msk
        if axis_name is not None:
            up_gid = jax.lax.all_gather(up_gid, axis_name).reshape(-1, k_max)
            ex_vals = jax.lax.all_gather(ex_vals, axis_name).reshape(-1, k_max, d)
            ex_msk = jax.lax.all_gather(ex_msk, axis_name).reshape(-1, k_max)

        # -- Eq. 3 over the global entity space (+1 padding segment); under
        # entity sharding this runs redundantly per shard on replicated inputs,
        # preserving the unsharded f32 summation order bit for bit.  The
        # existence weights are already existence x participation: absent or
        # undelivered messages arrive with mask 0, so a zero-participant round
        # produces an all-zero aggregate and priority — a no-op, not a NaN.
        agg, cnt = segment_aggregate(
            up_gid.reshape(-1),
            (ex_vals * ex_msk[:, :, None]).reshape(-1, d),
            ex_msk.reshape(-1),
            num_global + 1,
        )

        # -- personalized views: subtract the own wire-coded contribution
        agg_rows = agg[gid_blk] - own_wire
        pri_rows = (cnt[gid_blk] - uploaded_contrib) * validf
    with scope("feds/comm/download"):
        # downstream leg crosses the wire too
        agg_rows = codec.roundtrip(agg_rows.reshape(-1, d)).reshape(cl, ns_blk, d)

        # -- downstream Top-K by priority; jitter < 1 never reorders priorities
        rank = jnp.where(valid_blk, pri_rows + jitter_blk, -1.0)
        dn_idx = top_k_select(rank, k_max, entity_axis=ea)
        dn_mask = (slot < k[:, None]) & (
            eshard.dist_take_vec(pri_rows, dn_idx, ea) > 0
        )
        if faults is not None:
            # the server only selects (and bills) rows for participating clients
            dn_mask = dn_mask & (faults.part[:, None] > 0.5)
        sign = eshard.scatter_add_vec(
            jnp.zeros((cl, ns_blk), jnp.int8), dn_idx, dn_mask.astype(jnp.int8), ea
        )
        down_count = dn_mask.sum(axis=1).astype(jnp.int32)
        if faults is not None:
            # a lost download never lands; the bytes were still sent (and the
            # down_count above — which drives the ledger — already charged them)
            sign = sign * (faults.dn_ok[:, None] > 0.5).astype(jnp.int8)

        # -- Eq. 4 masked row update, fused over the flattened client axis
        new_emb = kernel_ops.sparse_apply(
            emb.reshape(-1, d),
            agg_rows.reshape(-1, d),
            pri_rows.reshape(-1),
            sign.reshape(-1),
        ).reshape(cl, ns_blk, d).astype(emb.dtype)
    out = (new_emb, new_hist, down_count)
    if res is not None:
        out = out + (new_res,)
    if queue is not None:
        out = out + (new_queue,)
    if prev is not None:
        prev_idx, prev_msk = prev
        up_idx32 = up_idx.astype(jnp.int32)
        if faults is None:
            partf = up_okf = dn_okf = jnp.ones((cl,), emb.dtype)
            new_prev = (up_idx32, up_maskf)
        else:
            partf, up_okf, dn_okf = faults.part, faults.up_ok, faults.dn_ok
            # the carry tracks the last upload actually SENT: absent clients
            # keep their previous selection
            partb = partf[:, None] > 0.5
            new_prev = (
                jnp.where(partb, up_idx32, prev_idx),
                jnp.where(partb, up_maskf, prev_msk),
            )
        if new_res is not None:
            res_mass = residual_mass(new_res, entity_axis=ea)
        else:
            res_mass = jnp.zeros((cl,), emb.dtype)
        # model-health probes run on full-width (all-blocks) buffers so the
        # divergence segment sums keep the unsharded summation order (the
        # batched_sync_round rule); nonfinite is integer, hence order-exact
        new_full = eshard.all_blocks(new_emb, ea)
        div_mean, div_max = shared_divergence(
            new_full, gid, valid, num_global, axis_name=axis_name
        )
        rec = RoundTelemetry(
            up_rows=sent_maskf.sum(axis=1).astype(jnp.int32),
            dn_rows=down_count,
            overlap=upload_overlap(up_idx, sent_maskf, prev_idx, prev_msk),
            res_mass=res_mass,
            part=partf,
            up_ok=up_okf,
            dn_ok=dn_okf,
            # ages live in FaultArrays; the cycle engines overwrite this
            # placeholder with the post-update counters
            age=jnp.zeros((cl,), jnp.int32),
            score_hist=score_histogram(scores, valid_blk, entity_axis=ea),
            div_mean=div_mean,
            div_max=div_max,
            upd_norm=update_norm(new_full, eshard.all_blocks(emb, ea), valid),
            nonfinite=nonfinite_count(new_full, valid),
        )
        out = out + (rec, new_prev)
    return out


@scope("feds/comm/sync")
def batched_sync_round(
    emb: jnp.ndarray,  # (C_local, Ns_max, D)
    gid: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    num_global: int,
    axis_name: Optional[str],
    entity_axis: Optional[str] = None,
    faults=None,  # Optional[repro.core.faults.RoundFaults] of (C_local,) masks
):
    """Intermittent synchronization (§III-E): FedE mean over owning clients.

    Returns (synchronized rows, refreshed history).  History is the PRE-sync
    rows — the protocol refreshes it with what was uploaded, matching
    :func:`repro.core.protocol.full_upload`.

    With ``faults``, the mean runs over delivered uploads only
    (``part * up_ok`` existence weights) and lands only on clients that
    participate and receive (``part * dn_ok``) — the recovery point for
    clients that missed the span.  Entities whose every owner is absent this
    round keep their rows: the ``cnt > 0`` guard below masks them out of the
    mean instead of writing the clamped-denominator zero row (the latent
    zero-participant divide-by-zero edge in the Eq. 3 weight normalization;
    unreachable without faults since every valid row contributes itself,
    so the guard changes nothing in fault-free programs).

    With ``entity_axis``, ``emb`` is this shard's slot block; the blocks are
    all-gathered once and the Eq. 3-style segment mean computed redundantly
    per shard in the unsharded summation order (a per-shard partial sum +
    f32 psum would reorder the additions and break the bitwise contract),
    then each shard keeps its local slice of the synchronized rows.  Over
    the client axis the sums are added in client order by
    :func:`client_ordered_aggregate`.
    """
    blk = emb.shape[1]
    emb_full = eshard.all_blocks(emb, entity_axis)
    cl, ns, d = emb_full.shape
    validf = valid.astype(emb.dtype)
    if faults is not None:
        validf = validf * (faults.part * faults.up_ok)[:, None]
    ids = jnp.where(valid, gid, num_global)
    rows = emb_full * validf[:, :, None]
    if axis_name is None:
        total, cnt = segment_aggregate(
            ids.reshape(-1), rows.reshape(-1, d), validf.reshape(-1),
            num_global + 1,
        )
    else:
        total, cnt = client_ordered_aggregate(
            ids, rows, validf, num_global + 1, axis_name
        )
    mean = total / jnp.maximum(cnt, 1.0)[:, None]
    live = valid & (cnt[gid] > 0)
    if faults is not None:
        live = live & ((faults.part * faults.dn_ok)[:, None] > 0.5)
    new_emb = jnp.where(live[:, :, None], mean[gid], emb_full)
    if entity_axis is None:
        return new_emb, emb
    return eshard.local_block(new_emb, entity_axis, blk), emb


# --------------------------------------------------------------------------
class RoundEngine:
    """Compiled FedS communication rounds over batched client state.

    Built once per federation from the static comm views; per round the
    caller gathers each client's current entity table into the padded batch,
    runs :meth:`sparse_round` / :meth:`sync_round`, and scatters the result
    back.  With ``mesh=None`` the round is a single-device jit; with a mesh
    it is ``shard_map``-ped over the client axis (C must be divisible by the
    mesh size).
    """

    def __init__(
        self,
        views: Sequence,  # list[repro.core.protocol.ClientCommView]
        num_global_entities: int,
        dim: int,
        sparsity_p: float,
        codec: Optional[WireCodec] = None,
        mesh=None,
        axis_name: str = "clients",
        telemetry: bool = False,
    ):
        self.views = list(views)
        self._tel = bool(telemetry)
        self.num_global = int(num_global_entities)
        self.dim = int(dim)
        self.codec = codec if codec is not None else IdentityCodec()
        if self.codec.has_residual:
            raise ValueError(
                f"codec {self.codec!r} carries error-feedback residual state; "
                "RoundEngine is stateless per round — use CycleEngine/"
                "SuperstepEngine, which thread residuals through "
                "FederationState"
            )
        self.num_clients = len(self.views)
        gid, valid, self.k_per_client, self.ns_max, self.k_max = build_padded_views(
            self.views, self.num_global, sparsity_p
        )
        self._gid = jnp.asarray(gid)
        self._valid = jnp.asarray(valid)
        self._k = jnp.asarray(self.k_per_client)

        axis = axis_name if mesh is not None else None
        sparse_core = functools.partial(
            batched_sparse_round, k_max=self.k_max, num_global=self.num_global,
            codec=self.codec, axis_name=axis,
        )
        sync_core = functools.partial(
            batched_sync_round, num_global=self.num_global, axis_name=axis,
        )
        def sparse_faulted(emb, hist, gid, valid, k, jitter, part, up_ok, dn_ok):
            from repro.core.faults import RoundFaults

            return sparse_core(
                emb, hist, gid, valid, k, jitter,
                faults=RoundFaults(part, up_ok, dn_ok),
            )

        def sparse_tel(emb, hist, gid, valid, k, jitter, prev_idx, prev_msk):
            return sparse_core(
                emb, hist, gid, valid, k, jitter, prev=(prev_idx, prev_msk)
            )

        def sparse_faulted_tel(
            emb, hist, gid, valid, k, jitter, part, up_ok, dn_ok,
            prev_idx, prev_msk,
        ):
            from repro.core.faults import RoundFaults

            return sparse_core(
                emb, hist, gid, valid, k, jitter,
                faults=RoundFaults(part, up_ok, dn_ok),
                prev=(prev_idx, prev_msk),
            )

        def sync_faulted(emb, gid, valid, part, up_ok, dn_ok):
            from repro.core.faults import RoundFaults

            return sync_core(
                emb, gid, valid, faults=RoundFaults(part, up_ok, dn_ok)
            )

        if mesh is None:
            self._sparse = jax.jit(sparse_core)
            self._sync = jax.jit(sync_core)
            self._sparse_faulted = jax.jit(sparse_faulted)
            self._sync_faulted = jax.jit(sync_faulted)
            if self._tel:
                self._sparse_tel = jax.jit(sparse_tel)
                self._sparse_faulted_tel = jax.jit(sparse_faulted_tel)
        else:
            p = jax.sharding.PartitionSpec(axis_name)
            self._sparse = jax.jit(jax.shard_map(
                sparse_core, mesh=mesh,
                in_specs=(p, p, p, p, p, p), out_specs=(p, p, p),
            ))
            self._sync = jax.jit(jax.shard_map(
                sync_core, mesh=mesh, in_specs=(p, p, p), out_specs=(p, p),
            ))
            self._sparse_faulted = jax.jit(jax.shard_map(
                sparse_faulted, mesh=mesh,
                in_specs=(p,) * 9, out_specs=(p, p, p),
            ))
            self._sync_faulted = jax.jit(jax.shard_map(
                sync_faulted, mesh=mesh,
                in_specs=(p,) * 6, out_specs=(p, p),
            ))
            if self._tel:
                ts = telemetry_record_spec(p)
                self._sparse_tel = jax.jit(jax.shard_map(
                    sparse_tel, mesh=mesh,
                    in_specs=(p,) * 8, out_specs=(p, p, p, ts, (p, p)),
                ))
                self._sparse_faulted_tel = jax.jit(jax.shard_map(
                    sparse_faulted_tel, mesh=mesh,
                    in_specs=(p,) * 11, out_specs=(p, p, p, ts, (p, p)),
                ))

    # ------------------------------------------------------- host transfers
    def gather(self, tables: Sequence) -> jnp.ndarray:
        """Stack each client's shared-entity rows into (C, Ns_max, D)."""
        out = np.zeros((self.num_clients, self.ns_max, self.dim), np.float32)
        for c, (v, t) in enumerate(zip(self.views, tables)):
            if v.num_shared:
                out[c, : v.num_shared] = np.asarray(t, np.float32)[v.shared_local]
        return jnp.asarray(out)

    def scatter(self, batch: jnp.ndarray, tables: Sequence) -> list:
        """Write the updated shared rows back into each client's full table."""
        out = []
        for c, (v, t) in enumerate(zip(self.views, tables)):
            if v.num_shared:
                rows = batch[c, : v.num_shared].astype(t.dtype)
                t = t.at[jnp.asarray(v.shared_local)].set(rows)
            out.append(t)
        return out

    # --------------------------------------------------------------- rounds
    def sparse_round(
        self,
        emb: jnp.ndarray,  # (C, Ns_max, D)
        hist: jnp.ndarray,  # (C, Ns_max, D)
        jitter: Optional[jnp.ndarray] = None,  # (C, Ns_max) in [0, 1)
        faults=None,  # Optional[repro.core.faults.RoundFaults] of (C,) masks
        prev=None,  # telemetry carry (requires telemetry=True at init)
    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """One sparse FedS round.  Returns (emb', hist', down_count (C,)),
        plus ``(RoundTelemetry, prev')`` when a telemetry carry is passed.

        ``faults`` injects per-round participation / message-drop masks
        (:mod:`repro.core.faults`).  RoundEngine is stateless per round, so
        straggler queues (which need carried state) are the cycle engines'
        job — exactly like EF residuals; the telemetry overlap carry is
        likewise the *caller's* state, threaded explicitly via ``prev``.
        """
        if prev is not None and not self._tel:
            raise ValueError("pass telemetry=True at construction to record")
        if jitter is None:
            jitter = jnp.zeros((self.num_clients, self.ns_max), jnp.float32)
        # halve after the f32 cast: float64 values in [1-2^-25, 1) round to
        # exactly 1.0f, which would tie with the next priority level
        jitter = jnp.asarray(jitter, jnp.float32) * 0.5
        if faults is None:
            if prev is None:
                return self._sparse(
                    emb, hist, self._gid, self._valid, self._k, jitter
                )
            return self._sparse_tel(
                emb, hist, self._gid, self._valid, self._k, jitter,
                prev[0], prev[1],
            )
        masks = (
            jnp.asarray(faults.part, jnp.float32),
            jnp.asarray(faults.up_ok, jnp.float32),
            jnp.asarray(faults.dn_ok, jnp.float32),
        )
        if prev is None:
            return self._sparse_faulted(
                emb, hist, self._gid, self._valid, self._k, jitter, *masks
            )
        return self._sparse_faulted_tel(
            emb, hist, self._gid, self._valid, self._k, jitter, *masks,
            prev[0], prev[1],
        )

    def sync_round(
        self, emb: jnp.ndarray, faults=None
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """One full-synchronization round.  Returns (emb', hist')."""
        if faults is None:
            return self._sync(emb, self._gid, self._valid)
        return self._sync_faulted(
            emb, self._gid, self._valid,
            jnp.asarray(faults.part, jnp.float32),
            jnp.asarray(faults.up_ok, jnp.float32),
            jnp.asarray(faults.dn_ok, jnp.float32),
        )
