"""Device-resident federation state and the fused train+communicate cycle.

:class:`repro.core.engine.RoundEngine` (PR 1) made the FedS communication
round one compiled program, but the simulation still paid host costs every
round: each client's entity table was gathered/scattered through numpy, and
local training re-stacked numpy batches per epoch in front of a per-client
jit.  This module removes both:

* :class:`FederationState` holds the WHOLE federation on device across
  rounds — padded ``(C, E_max, D)`` entity tables, ``(C, R, Rd)`` relation
  tables, the stacked Adam state, the ``(C, Ns_max, D)`` upload history, the
  ``(C, Ns_max, D)`` codec error-feedback residuals (see
  :mod:`repro.core.codecs`), and a threaded ``jax.random`` key (replacing
  the host-side numpy jitter RNG).
  It is built once from the per-client state and only scattered back to the
  clients at eval/snapshot boundaries (:meth:`CycleEngine.sync_clients`).
* :class:`CycleEngine` compiles one *cycle* — ``local_epochs`` of the
  training ``lax.scan`` with all batches pre-sampled on device, followed by
  the FedS sparse/sync round of :mod:`repro.core.engine` — as ONE ``jax.jit``
  program (host) or one ``shard_map`` program over the client axis (pod).
* :class:`SuperstepEngine` (PR 3) goes one level up: a whole Intermittent
  Synchronization Mechanism period — ``s`` sparse rounds then one dense sync
  round, as scheduled by :func:`repro.core.sync.round_kind` — is
  ``lax.scan``-ned into a SINGLE program per superstep, carrying the
  federation state, the threaded PRNG key, and device-side ledger
  accumulators (per-round download counts) through the scan.  One host
  touch-point per ``s+1`` rounds instead of one per round.

Client heterogeneity is expressed with static shapes throughout: triples are
padded to ``T_max`` (samplers draw indices below the true count), batches to
``B_max`` with zero-weight rows in the loss, and shared-entity rows to
``Ns_max`` exactly as in the round engine.  Unequal batches-per-epoch are
trained as static segments (:func:`step_segments`): each segment scans only
the clients that still have real steps.  Under a mesh, where every shard runs
one program over its own clients, the scan instead pads to
``local_epochs * S_max`` steps with pass-through optimizer steps
(:func:`repro.train.optimizer.masked_adam_update`).

The per-round oracle path (``engine="batched"`` in the simulation) runs the
SAME ``train_core`` / ``comm_core`` functions as two separate jits per round,
so the property tests can assert that fusing them into one program changes
nothing (same seeds -> same eval trajectory and ledger totals).  See
EXPERIMENTS.md §Cycle.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import TYPE_CHECKING, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import eshard
from repro.core.codecs import IdentityCodec, WireCodec
from repro.core.engine import (
    batched_sparse_round,
    batched_sync_round,
    build_padded_views,
)
from repro.core.evaluation import EvalBank
from repro.core.faults import (
    FaultArrays,
    FaultSchedule,
    RoundFaults,
    draw_round_faults,
    init_fault_arrays,
)
from repro.core.sync import compress_schedule
from repro.core.telemetry import (
    NUM_SCORE_BUCKETS,
    RoundTelemetry,
    TelemetryArrays,
    init_telemetry_arrays,
    nonfinite_count,
    record_spec,
    residual_mass,
    scope,
    shared_divergence,
    span,
    telemetry_spec,
    update_norm,
)
from repro.data.loader import stack_padded_triples
from repro.kge.scoring import get_scoring, loss_from_scores, per_sample_losses
from repro.train.optimizer import AdamState, adam_update, masked_adam_update

if TYPE_CHECKING:  # core never imports federated at runtime (layering)
    from repro.federated.client import KGEClient


class StateArrays(NamedTuple):
    """Device-resident pytree; every leaf leads with the client axis, so one
    ``PartitionSpec('clients')`` prefix shards the whole bundle."""

    params: dict  # {"entity": (C, E_max, D), "relation": (C, R, Rd)}
    opt: AdamState  # step (C,), mu/nu mirroring params
    hist: jnp.ndarray  # (C, Ns_max, D) upload history of shared rows
    res: jnp.ndarray  # (C, Ns_max, D) codec error-feedback residuals,
    #                   cleared by sync rounds; (C, 0, D) empty placeholder
    #                   when the codec carries no residual, so non-EF runs
    #                   pay no scan-carry traffic for it
    faults: FaultArrays  # per-client staleness counters + the straggler
    #                      in-flight message queue (repro.core.faults);
    #                      zero-width queue when the schedule has no
    #                      stragglers, passed through untouched when the
    #                      engine has no active fault schedule at all
    tel: Optional[TelemetryArrays] = None  # flight-recorder overlap carry
    #                      (repro.core.telemetry): the previous round's sent
    #                      upload selection.  None — zero pytree leaves —
    #                      with telemetry off, so untelemetered runs compile
    #                      exactly the historical programs (the same static
    #                      gating as trivial fault schedules)


class CycleConsts(NamedTuple):
    """Static per-federation device constants.

    Client-axis leading like the state, and passed as explicit program
    arguments (NOT closed over) so ``shard_map`` slices them per shard."""

    cids: jnp.ndarray  # (C,) global client index, for per-client key folding
    triples: jnp.ndarray  # (C, T_max, 3) padded local training triples
    num_train: jnp.ndarray  # (C,) true triple counts
    num_ent: jnp.ndarray  # (C,) local entity counts (negative-sampling bound)
    sample_w: jnp.ndarray  # (C, B_max) f32 0/1 padded-batch-row weights
    step_mask: jnp.ndarray  # (C, L) valid scan steps
    gather_idx: jnp.ndarray  # (C, Ns_max) local row per shared slot (0 padded)
    scatter_idx: jnp.ndarray  # (C, Ns_max) same, E_max sentinel on padding
    gid: jnp.ndarray  # (C, Ns_max) global entity ids (num_global padded)
    valid: jnp.ndarray  # (C, Ns_max) shared-slot validity
    k: jnp.ndarray  # (C,) per-client upstream/downstream K
    straggler: jnp.ndarray  # (C,) f32 static straggler-set indicator


def step_segments(steps, local_epochs: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """Static schedule of the real client-steps of one round.

    Client ``c`` trains ``n_c = local_epochs * steps[c]`` real steps; its
    ``j``-th is index ``e * S_max + i`` of the padded ``local_epochs * S_max``
    scan, where ``j = e * steps[c] + i``.  With ``0 = b_0 < b_1 < ... < b_m``
    the distinct ``n_c``, segment ``k`` covers positions ``[b_{k-1}, b_k)``
    of every client with ``n_c >= b_k``.  Returns one ``(clients, idx)`` per
    segment: the active client ids, ascending, and their
    ``(len(clients), b_k - b_{k-1})`` scan indices.
    """
    steps = np.asarray(steps, np.int64)
    s_max = int(steps.max())
    n = local_epochs * steps
    out, lo = [], 0
    for hi in np.unique(n[n > 0]):
        clients = np.flatnonzero(n >= hi)
        j = np.arange(lo, hi)[None, :]
        per = steps[clients][:, None]
        out.append((clients.astype(np.int32),
                    ((j // per) * s_max + j % per).astype(np.int32)))
        lo = int(hi)
    return out


@dataclasses.dataclass
class FederationState:
    """The whole federation, on device, between host touch-points."""

    arrays: StateArrays
    key: jax.Array  # threaded PRNG key: one 3-way split per cycle


class CycleEngine:
    """Compiled train+communicate cycles over :class:`FederationState`.

    Built once per federation from the clients (hyper-parameters must be
    homogeneous).  ``mesh=None`` compiles single-device jits; with a 1-D mesh
    the same programs run under ``shard_map`` over the client axis (C must be
    divisible by the mesh size), the only collective being the round's
    all-gather of the clients' uploads (sparse) or the client-ordered sum
    of their shared rows (sync).
    """

    def __init__(
        self,
        clients: Sequence["KGEClient"],
        views: Sequence,  # list[repro.core.protocol.ClientCommView]
        num_global_entities: int,
        *,
        sparsity_p: float,
        local_epochs: int,
        codec: Optional[WireCodec] = None,
        mesh=None,
        axis_name: str = "clients",
        entity_axis: Optional[str] = None,
        faults: Optional[FaultSchedule] = None,
        telemetry: bool = False,
    ):
        self.views = list(views)
        self.num_global = int(num_global_entities)
        self.num_clients = len(clients)
        # static, like the trivial-schedule gate: telemetry=False builds the
        # exact historical programs (no record outputs, no overlap carry)
        self._tel = bool(telemetry)
        # a trivial schedule compiles EXACTLY the fault-free programs — the
        # all-present case is bitwise pre-fault by construction, not by test
        self._sched = (
            faults if (faults is not None and not faults.trivial) else None
        )
        if self._sched is not None:
            self._sched.validate_clients(self.num_clients)
        if self.num_clients != len(self.views):
            raise ValueError("one comm view per client required")
        c0 = clients[0]
        self.method = c0.method
        self.gamma = float(c0.gamma)
        self.lr = float(c0.lr)
        self.temp = float(c0.temp)
        self.dim = int(c0.model.dim)
        self.rel_dim = int(c0.model.rel_dim)
        self.num_relations = int(c0.model.num_relations)
        self.local_epochs = int(local_epochs)
        self.num_negatives = int(c0.loader.num_negatives)
        self.codec = codec if codec is not None else IdentityCodec()
        for c in clients:
            if (
                c.method != self.method
                or c.model.dim != self.dim
                or c.model.num_relations != self.num_relations
                or c.loader.num_negatives != self.num_negatives
                or (float(c.gamma), float(c.lr), float(c.temp))
                != (self.gamma, self.lr, self.temp)
            ):
                raise ValueError(
                    "CycleEngine requires homogeneous model/loader hyper-parameters"
                )

        gid, valid, self.k_per_client, self.ns_max, self.k_max = build_padded_views(
            self.views, self.num_global, sparsity_p
        )

        # entity-axis sharding: every row-major table — entity embeddings and
        # Adam moments along E, upload history / EF residuals along Ns — is
        # block-sharded over the mesh's second axis.  Row counts pad up so
        # the blocks split evenly (E additionally to whole 32-entity filter
        # words, so the eval word axis shards evenly too); the padding slots
        # are invalid/zero rows the round and trainer masks already ignore.
        self._eaxis = entity_axis if mesh is not None else None
        if self._eaxis is not None and self._eaxis not in dict(mesh.shape):
            raise ValueError(
                f"entity_axis {self._eaxis!r} not in mesh axes {tuple(mesh.shape)}"
            )
        n_e = int(dict(mesh.shape)[self._eaxis]) if self._eaxis else 1
        self.n_eshards = n_e
        self.ns_pad = eshard.pad_rows(self.ns_max, n_e) if n_e > 1 else self.ns_max
        if self.ns_pad > self.ns_max:
            pad = self.ns_pad - self.ns_max
            gid = np.pad(gid, ((0, 0), (0, pad)), constant_values=self.num_global)
            valid = np.pad(valid, ((0, 0), (0, pad)))

        self.num_entities = np.asarray(
            [c.model.num_entities for c in clients], np.int32
        )
        self.e_max = int(self.num_entities.max())
        self.e_pad = (
            eshard.pad_rows(self.e_max, n_e, 32) if n_e > 1 else self.e_max
        )
        triples, counts = stack_padded_triples([c.data.train for c in clients])
        batch_sizes = np.asarray([c.loader.batch_size for c in clients], np.int32)
        steps = np.asarray([c.loader.batches_per_epoch for c in clients], np.int32)
        self.b_max = int(batch_sizes.max())
        self.s_max = int(steps.max())
        self.scan_len = self.local_epochs * self.s_max
        sample_w = (
            np.arange(self.b_max)[None, :] < batch_sizes[:, None]
        ).astype(np.float32)
        # step i of the flattened epochs*S_max scan belongs to epoch-position
        # i % S_max; clients with fewer batches-per-epoch pass through.
        step_mask = (
            np.tile(np.arange(self.s_max), self.local_epochs)[None, :]
            < steps[:, None]
        )
        # Static fast paths: when every client has the same batches-per-epoch
        # (resp. batch size) the masks are all-ones and the per-step
        # pass-through selects / per-sample weights — full-table-sized
        # ``where``s — are dead weight, so they are compiled out entirely.
        self._uniform_steps = bool(step_mask.all())
        self._uniform_batches = bool((sample_w == 1.0).all())
        # Unequal batches-per-epoch in one program over every client: the
        # trainer scans each segment of the static step schedule over the
        # clients that still have real steps, and computes no padding.
        # Under a mesh each shard runs one program over its own clients, so
        # no per-client schedule fits every shard: the masked scan stays.
        self._segments = (
            step_segments(steps, self.local_epochs)
            if not self._uniform_steps and mesh is None else None
        )
        # work counters of the ``round`` telemetry span, fixed here so a
        # span never reads the device: client-steps the trainer computes per
        # round (padding included where the masked scan runs), those that
        # train on a real batch, and the training scans a round runs
        self.client_steps_real = int(step_mask.sum())
        if self._segments is None:
            self.client_steps = self.num_clients * self.scan_len
            self.train_segments = 1
        else:
            self.client_steps = sum(idx.size for _, idx in self._segments)
            self.train_segments = len(self._segments)
        gather_idx = np.zeros((self.num_clients, self.ns_pad), np.int32)
        scatter_idx = np.full((self.num_clients, self.ns_pad), self.e_pad, np.int32)
        for c, v in enumerate(self.views):
            gather_idx[c, : v.num_shared] = v.shared_local
            scatter_idx[c, : v.num_shared] = v.shared_local
        self.consts = CycleConsts(
            cids=jnp.arange(self.num_clients, dtype=jnp.int32),
            triples=jnp.asarray(triples),
            num_train=jnp.asarray(counts),
            num_ent=jnp.asarray(self.num_entities),
            sample_w=jnp.asarray(sample_w),
            step_mask=jnp.asarray(step_mask),
            gather_idx=jnp.asarray(gather_idx),
            scatter_idx=jnp.asarray(scatter_idx),
            gid=jnp.asarray(gid),
            valid=jnp.asarray(valid),
            k=jnp.asarray(self.k_per_client),
            straggler=jnp.asarray(
                self._sched.straggler_mask(self.num_clients)
                if self._sched is not None
                else np.zeros((self.num_clients,), np.float32)
            ),
        )

        self._axis = axis_name if mesh is not None else None
        self._mesh = mesh
        train_core = self._make_train_core()
        comm_core = self._make_comm_core()
        # kept for SuperstepEngine, which re-composes the same cores into
        # multi-round scanned programs (the equivalence contract depends on
        # every engine mode running these exact functions)
        self._train_core_fn = train_core
        self._comm_core_fn = comm_core

        tel = self._tel

        def comm_sparse(arrays, jitter, consts):
            return comm_core(arrays, jitter, consts, do_sync=False)

        def comm_sync(arrays, consts):
            return comm_core(arrays, None, consts, do_sync=True)

        def fused(arrays, kb, kj, consts, do_sync):
            arrays, jitter, loss = train_core(arrays, kb, kj, consts)
            if tel:
                arrays, down, rec = comm_core(
                    arrays, jitter, consts, do_sync=do_sync
                )
                return arrays, down, loss, rec
            arrays, down = comm_core(arrays, jitter, consts, do_sync=do_sync)
            return arrays, down, loss

        fused_sparse = functools.partial(fused, do_sync=False)
        fused_sync = functools.partial(fused, do_sync=True)

        # fault-schedule variants: same cores, plus the absolute round index
        # t as a (traced) program input — the per-round masks are drawn
        # INSIDE the program as a pure function of t (repro.core.faults), so
        # host replay / the reference oracle / the scanned superstep all see
        # bit-identical schedules.  The full (C,) draw is replicated across
        # shards; consts.cids slices each shard's clients.
        sched = self._sched

        @scope("feds/comm")
        def round_faults_of(consts, t):
            rf = draw_round_faults(sched, t, self.num_clients)
            return RoundFaults(
                part=rf.part[consts.cids],
                up_ok=rf.up_ok[consts.cids],
                dn_ok=rf.dn_ok[consts.cids],
            )

        self._round_faults = round_faults_of if sched is not None else None

        def comm_sparse_f(arrays, jitter, consts, t):
            return comm_core(
                arrays, jitter, consts, do_sync=False,
                rf=round_faults_of(consts, t),
            )

        def comm_sync_f(arrays, consts, t):
            return comm_core(
                arrays, None, consts, do_sync=True,
                rf=round_faults_of(consts, t),
            )

        def fused_f(arrays, kb, kj, consts, t, do_sync):
            arrays, jitter, loss = train_core(arrays, kb, kj, consts)
            out = comm_core(
                arrays, jitter, consts, do_sync=do_sync,
                rf=round_faults_of(consts, t),
            )
            if tel:
                arrays, down, rec = out
                return arrays, down, loss, rec
            arrays, down = out
            return arrays, down, loss

        fused_sparse_f = functools.partial(fused_f, do_sync=False)
        fused_sync_f = functools.partial(fused_f, do_sync=True)

        if mesh is None:
            # State flows linearly cycle-to-cycle, so the big resident
            # buffers (entity tables, Adam moments, history) are donated —
            # XLA updates them in place instead of allocating fresh ones.
            self._train = jax.jit(train_core, donate_argnums=(0,))
            self._comm_sparse = jax.jit(comm_sparse, donate_argnums=(0,))
            self._comm_sync = jax.jit(comm_sync, donate_argnums=(0,))
            self._fused_sparse = jax.jit(fused_sparse, donate_argnums=(0,))
            self._fused_sync = jax.jit(fused_sync, donate_argnums=(0,))
            if sched is not None:
                self._comm_sparse_f = jax.jit(comm_sparse_f, donate_argnums=(0,))
                self._comm_sync_f = jax.jit(comm_sync_f, donate_argnums=(0,))
                self._fused_sparse_f = jax.jit(fused_sparse_f, donate_argnums=(0,))
                self._fused_sync_f = jax.jit(fused_sync_f, donate_argnums=(0,))
        else:
            n_c = int(dict(mesh.shape)[axis_name])
            if self.num_clients % n_c != 0:
                raise ValueError(
                    f"{self.num_clients} clients not divisible by "
                    f"{n_c} client-axis mesh devices"
                )
            pa = self._arrays_spec()  # StateArrays-shaped (or plain prefix)
            p = jax.sharding.PartitionSpec(axis_name)
            r = jax.sharding.PartitionSpec()
            # record leaves are all client-axis-leading and psum-replicated
            # over any entity axis, so one client-only spec covers the pytree
            comm_out = (pa, p, record_spec(p)) if tel else (pa, p)
            fused_out = (pa, p, p, record_spec(p)) if tel else (pa, p, p)
            self._train = jax.jit(jax.shard_map(
                train_core, mesh=mesh, in_specs=(pa, r, r, p), out_specs=(pa, p, p),
            ), donate_argnums=(0,))
            self._comm_sparse = jax.jit(jax.shard_map(
                comm_sparse, mesh=mesh, in_specs=(pa, p, p), out_specs=comm_out,
            ), donate_argnums=(0,))
            self._comm_sync = jax.jit(jax.shard_map(
                comm_sync, mesh=mesh, in_specs=(pa, p), out_specs=comm_out,
            ), donate_argnums=(0,))
            self._fused_sparse = jax.jit(jax.shard_map(
                fused_sparse, mesh=mesh, in_specs=(pa, r, r, p),
                out_specs=fused_out,
            ), donate_argnums=(0,))
            self._fused_sync = jax.jit(jax.shard_map(
                fused_sync, mesh=mesh, in_specs=(pa, r, r, p),
                out_specs=fused_out,
            ), donate_argnums=(0,))
            if sched is not None:
                self._comm_sparse_f = jax.jit(jax.shard_map(
                    comm_sparse_f, mesh=mesh, in_specs=(pa, p, p, r),
                    out_specs=comm_out,
                ), donate_argnums=(0,))
                self._comm_sync_f = jax.jit(jax.shard_map(
                    comm_sync_f, mesh=mesh, in_specs=(pa, p, r),
                    out_specs=comm_out,
                ), donate_argnums=(0,))
                self._fused_sparse_f = jax.jit(jax.shard_map(
                    fused_sparse_f, mesh=mesh, in_specs=(pa, r, r, p, r),
                    out_specs=fused_out,
                ), donate_argnums=(0,))
                self._fused_sync_f = jax.jit(jax.shard_map(
                    fused_sync_f, mesh=mesh, in_specs=(pa, r, r, p, r),
                    out_specs=fused_out,
                ), donate_argnums=(0,))

    def _arrays_spec(self):
        """PartitionSpec pytree for :class:`StateArrays` under the mesh.

        Client-only sharding keeps the historical single-spec prefix; with
        an entity axis the row-sharded leaves (entity table + its Adam
        moments, history, residuals) get the 2-D ``(clients, entities)``
        spec while relation tables and step counts stay client-only.
        """
        p = jax.sharding.PartitionSpec(self._axis)
        if self._eaxis is None:
            return p
        pe = jax.sharding.PartitionSpec(self._axis, self._eaxis)
        ent_like = {"entity": pe, "relation": p}
        return StateArrays(
            params=ent_like,
            opt=AdamState(step=p, mu=dict(ent_like), nu=dict(ent_like)),
            hist=pe,
            res=pe,
            # fault state is small and per-client (queue values are gathered
            # full rows, already entity-replicated) — client-only sharding
            faults=FaultArrays(age=p, q_idx=p, q_val=p, q_msk=p),
            # overlap carry is (C, k_max) slot indices — client-only too
            tel=telemetry_spec(p) if self._tel else None,
        )

    def _bank_spec(self):
        """PartitionSpec pytree for :class:`EvalBank` under the mesh —
        packed filter words row-shard on the word axis (32 rows per word,
        and every entity block is 32-aligned, so the split is exact)."""
        p = jax.sharding.PartitionSpec(self._axis)
        if self._eaxis is None:
            return p
        pw = jax.sharding.PartitionSpec(self._axis, None, self._eaxis)
        return EvalBank(triples=p, count=p, ft_words=pw, fh_words=pw, num_ent=p)

    # ------------------------------------------------------- program bodies
    def _make_train_core(self):
        scan_len, b_max, n_neg = self.scan_len, self.b_max, self.num_negatives
        method, gamma, lr, temp = self.method, self.gamma, self.lr, self.temp
        ns_max, ns_pad = self.ns_max, self.ns_pad
        uniform_steps = self._uniform_steps
        uniform_batches = self._uniform_batches
        eaxis, n_eshards = self._eaxis, self.n_eshards
        segments, n_clients = self._segments, self.num_clients

        def sample_one(cid, tri, t_c, e_c, kb):
            """Pre-sample the whole cycle's batches for one client on device."""
            kc = jax.random.fold_in(kb, cid)
            pi = jax.random.randint(
                jax.random.fold_in(kc, 1), (scan_len, b_max), 0, t_c
            )
            pos = jnp.take(tri, pi, axis=0)  # (L, B, 3)
            neg_t = jax.random.randint(
                jax.random.fold_in(kc, 2), (scan_len, b_max, n_neg), 0, e_c
            )
            neg_h = jax.random.randint(
                jax.random.fold_in(kc, 3), (scan_len, b_max, n_neg), 0, e_c
            )
            return pos, neg_t, neg_h

        # registry-routed scoring: the spec's jit-safe score piece plus the
        # family-tagged loss weighting inside per_sample_losses below
        score = get_scoring(self.method).score

        def scores_of(rows, rel, cb):
            """Scores from ONE gathered row block ``[h; t; neg_t; neg_h]``."""
            h_e, t_e = rows[:cb], rows[cb : 2 * cb]
            nt_e = rows[2 * cb : (2 + n_neg) * cb].reshape(cb, n_neg, -1)
            nh_e = rows[(2 + n_neg) * cb :].reshape(cb, n_neg, -1)
            pos_score = score(h_e, rel, t_e, gamma)
            neg_t_score = score(h_e[:, None, :], rel[:, None, :], nt_e, gamma)
            neg_h_score = score(nh_e, rel[:, None, :], t_e[:, None, :], gamma)
            return pos_score, jnp.concatenate([neg_t_score, neg_h_score], -1)

        # Both trainers below compute gradients with respect to the GATHERED
        # rows and scatter-add the cotangents back ONCE: differentiating the
        # table-indexing loss directly materializes a dense (E, D) cotangent
        # per gather (six of them), which at FB15k scale costs ~20x the batch
        # math itself.  Same gradient, summation order aside.

        # ---- flat fast path: the client axis folds into the row axis, so
        # every gather/scatter is a fast single-level op (a batched scatter
        # under vmap falls off XLA:CPU's fast path).  Valid whenever all
        # clients share batches-per-epoch; per-client Adam bias correction
        # then reduces to one shared step count (taken from client 0, all
        # equal by construction).
        def train_flat(params, opt, pos, neg_t, neg_h, s_w):
            c_n, e_m, d = params["entity"].shape
            r_n, r_d = params["relation"].shape[1:]
            cb = c_n * b_max
            flat = lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])  # noqa: E731
            params_f = jax.tree.map(flat, params)
            opt_f = AdamState(
                step=opt.step[0],
                mu=jax.tree.map(flat, opt.mu),
                nu=jax.tree.map(flat, opt.nu),
            )
            roff = jnp.arange(c_n, dtype=jnp.int32) * r_n
            # objective = sum over clients of each client's (weighted) mean
            # loss — cross-client gradients are disjoint, so one backward
            # pass yields every client's own-mean gradient.
            if uniform_batches:
                wn = jnp.full((c_n, b_max), 1.0 / b_max, jnp.float32)
            else:
                wn = s_w / jnp.maximum(s_w.sum(axis=1, keepdims=True), 1.0)

            # client id of every row of the flattened [h; t; neg_t; neg_h]
            # gather list — the entity-sharded gather/scatter keys on
            # (client, entity) pairs instead of pre-folded flat indices
            cid_rows = jnp.concatenate(
                [jnp.repeat(jnp.arange(c_n, dtype=jnp.int32), b_max)] * 2
                + [jnp.repeat(jnp.arange(c_n, dtype=jnp.int32), b_max * n_neg)] * 2
            )

            def gather_rows(table, e_idx):
                """rows ``table[c * E + e]`` with E row-sharded; exact."""
                if eaxis is None:
                    return table[cid_rows * e_m + e_idx]
                base = jax.lax.axis_index(eaxis) * e_m  # e_m == local block
                loc = jnp.clip(e_idx - base, 0, e_m - 1)
                cand = table[cid_rows * e_m + loc]
                g = jax.lax.all_gather(cand, eaxis)  # (S, M, d)
                owner = jnp.clip(e_idx // e_m, 0, n_eshards - 1)
                out = jnp.take_along_axis(
                    jnp.moveaxis(g, 0, 1), owner[:, None, None], axis=1
                )
                return out[:, 0]

            def scatter_grads(table, e_idx, g_rows):
                """Drop-mode scatter-add of owned contributions, full-list
                order — per-row accumulation order matches unsharded."""
                if eaxis is None:
                    return jnp.zeros_like(table).at[cid_rows * e_m + e_idx].add(g_rows)
                base = jax.lax.axis_index(eaxis) * e_m
                loc = e_idx - base
                own = (loc >= 0) & (loc < e_m)
                flat = jnp.where(own, cid_rows * e_m + loc, c_n * e_m)
                return jnp.zeros_like(table).at[flat].add(g_rows, mode="drop")

            def step_fn(carry, x):
                params_f, opt_f = carry
                p, nt, nh = x  # (C, B, 3), (C, B, N)

                def loss_fn(rows, rel):
                    pos_s, neg_s = scores_of(rows, rel, cb)
                    per = per_sample_losses(pos_s, neg_s, method, temp)
                    loss_c = (per.reshape(c_n, b_max) * wn).sum(axis=1) / 2.0
                    return loss_c.sum(), loss_c

                with scope("feds/train/gather"):
                    r = (p[:, :, 1] + roff[:, None]).reshape(-1)
                    e_idx = jnp.concatenate([
                        p[:, :, 0].reshape(-1), p[:, :, 2].reshape(-1),
                        nt.reshape(-1), nh.reshape(-1),
                    ])
                    rows = gather_rows(params_f["entity"], e_idx)
                    rel = params_f["relation"][r]
                with scope("feds/train/score_grad"):
                    (_, loss_c), (g_rows, g_rel) = jax.value_and_grad(
                        loss_fn, argnums=(0, 1), has_aux=True
                    )(rows, rel)
                with scope("feds/train/scatter"):
                    rel_tab = params_f["relation"]
                    grads = {
                        "entity": scatter_grads(params_f["entity"], e_idx, g_rows),
                        "relation": jnp.zeros_like(rel_tab).at[r].add(g_rel),
                    }
                with scope("feds/train/adam"):
                    params_f, opt_f = adam_update(grads, opt_f, params_f, lr)
                return (params_f, opt_f), loss_c

            (params_f, opt_f), losses = jax.lax.scan(
                step_fn, (params_f, opt_f),
                (jnp.moveaxis(pos, 0, 1), jnp.moveaxis(neg_t, 0, 1),
                 jnp.moveaxis(neg_h, 0, 1)),
            )
            params = {
                "entity": params_f["entity"].reshape(c_n, e_m, d),
                "relation": params_f["relation"].reshape(c_n, r_n, r_d),
            }
            unflat = lambda t_: {  # noqa: E731
                "entity": t_["entity"].reshape(c_n, e_m, d),
                "relation": t_["relation"].reshape(c_n, r_n, r_d),
            }
            new_opt = AdamState(
                step=jnp.broadcast_to(opt_f.step, (c_n,)),
                mu=unflat(opt_f.mu),
                nu=unflat(opt_f.nu),
            )
            return params, new_opt, losses.mean(axis=0)

        # ---- unequal batches-per-epoch: vmap over clients of a per-client scan
        def batch_grads(params, p, nt, nh, weight):
            ent, rel_tab = params["entity"], params["relation"]

            def loss_fn(rows, rel):
                pos_s, neg_s = scores_of(rows, rel, b_max)
                return loss_from_scores(pos_s, neg_s, method, temp, weight)

            with scope("feds/train/gather"):
                h, r, t = p[:, 0], p[:, 1], p[:, 2]
                idx = jnp.concatenate([h, t, nt.reshape(-1), nh.reshape(-1)])
                if eaxis is None:
                    rows_in = ent[idx]
                else:  # collectives batch under the client vmap (one per shard)
                    rows_in = eshard._take_rows_one(ent, idx, eaxis)
                rel_in = rel_tab[r]
            with scope("feds/train/score_grad"):
                loss, (g_rows, g_rel) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1)
                )(rows_in, rel_in)
            with scope("feds/train/scatter"):
                if eaxis is None:
                    g_ent = jnp.zeros_like(ent).at[idx].add(g_rows)
                else:
                    e_blk = ent.shape[0]
                    loc = idx - jax.lax.axis_index(eaxis) * e_blk
                    own = (loc >= 0) & (loc < e_blk)
                    g_ent = jnp.zeros_like(ent).at[
                        jnp.where(own, loc, e_blk)
                    ].add(g_rows, mode="drop")
                grads = {
                    "entity": g_ent,
                    "relation": jnp.zeros_like(rel_tab).at[r].add(g_rel),
                }
            return loss, grads

        def train_one(params, opt, pos, neg_t, neg_h, s_w, s_mask=None):
            """One client's scan; ``s_mask`` flags its real steps, and a
            padded one passes params and Adam state through (None: every
            step is real).  Returns the per-step losses, 0 on padding."""
            weight = None if uniform_batches else s_w

            def step(carry, x):
                params, opt = carry
                p, nt, nh, ok = x
                loss, grads = batch_grads(params, p, nt, nh, weight)
                with scope("feds/train/adam"):
                    if ok is None:
                        params, opt = adam_update(grads, opt, params, lr)
                    else:
                        params, opt = masked_adam_update(
                            grads, opt, params, lr, ok
                        )
                if ok is not None:
                    loss = jnp.where(ok, loss, 0.0)
                return (params, opt), loss

            (params, opt), losses = jax.lax.scan(
                step, (params, opt), (pos, neg_t, neg_h, s_mask)
            )
            return params, opt, losses

        def contiguous(ids):
            """Ascending ``ids`` as a slice where they run without a gap."""
            lo, hi = int(ids[0]), int(ids[-1]) + 1
            return slice(lo, hi) if hi - lo == len(ids) else ids

        def rows(tree, sel):
            """``tree``'s leaves at client rows ``sel`` (None: all)."""
            return tree if sel is None else jax.tree.map(lambda a: a[sel], tree)

        def train_segmented(params, opt, pos, neg_t, neg_h, s_w):
            """Scan each segment of the static step schedule over its active
            clients, taken from and written back to the ``(C, ...)`` state.
            Every computed step is real, so Adam runs unmasked.  Returns the
            ``(C, L)`` per-step losses at their scan positions, 0 on
            padding, as the masked scan gives them."""
            losses = jnp.zeros((n_clients, scan_len), jnp.float32)
            for clients, idx in segments:
                sel = None if len(clients) == n_clients else contiguous(clients)
                if (idx == idx[:1]).all():  # one static step range for all
                    t = contiguous(idx[0])
                    xs = [rows(a, sel)[:, t] for a in (pos, neg_t, neg_h)]
                else:
                    xs = [a[clients[:, None], idx] for a in (pos, neg_t, neg_h)]
                p, o, seg_losses = jax.vmap(train_one)(
                    *rows((params, opt), sel), *xs, rows(s_w, sel)
                )
                if sel is None:
                    params, opt = p, o
                else:
                    params, opt = jax.tree.map(
                        lambda a, b: a.at[sel].set(b), (params, opt), (p, o)
                    )
                losses = losses.at[clients[:, None], idx].set(seg_losses)
            return params, opt, losses

        def map_relation(f, params, opt):
            """Apply ``f`` to the relation table and its Adam moments."""
            return (
                dict(params, relation=f(params["relation"])),
                opt._replace(
                    mu=dict(opt.mu, relation=f(opt.mu["relation"])),
                    nu=dict(opt.nu, relation=f(opt.nu["relation"])),
                ),
            )

        @scope("feds/train")
        def train_core(arrays, kb, kj, consts):
            with scope("feds/train/sample"):
                pos, neg_t, neg_h = jax.vmap(
                    sample_one, in_axes=(0, 0, 0, 0, None)
                )(consts.cids, consts.triples, consts.num_train,
                  consts.num_ent, kb)
            params, opt = arrays.params, arrays.opt
            if eaxis is not None:
                # every entity shard applies the same relation updates from
                # the same gathered rows: train them as per-shard copies (a
                # gradient against the replicated table would psum the
                # shards' identical contributions) and retype the result
                # replicated on the way out
                params, opt = map_relation(
                    lambda x: jax.lax.pcast(x, eaxis, to="varying"),
                    params, opt,
                )
            if uniform_steps:
                params, opt, loss = train_flat(
                    params, opt, pos, neg_t, neg_h, consts.sample_w
                )
            else:
                if segments is None:
                    params, opt, losses = jax.vmap(train_one)(
                        params, opt, pos, neg_t, neg_h,
                        consts.sample_w, consts.step_mask,
                    )
                else:
                    params, opt, losses = train_segmented(
                        params, opt, pos, neg_t, neg_h, consts.sample_w
                    )
                loss = losses.sum(axis=1) / jnp.maximum(
                    consts.step_mask.sum(axis=1), 1
                )
            if eaxis is not None:
                params, opt = map_relation(
                    lambda x: eshard.replicated(x, eaxis), params, opt
                )
                loss = eshard.replicated(loss, eaxis)
            # Downstream tie-break jitter for the round that follows; computed
            # here so the per-round oracle consumes bit-identical noise.
            # Always drawn at the LOGICAL ns_max shape — the draw shape feeds
            # the PRNG, so padding must happen after, not in the draw.
            with scope("feds/train/sample"):
                jitter = jax.vmap(
                    lambda cid: jax.random.uniform(
                        jax.random.fold_in(kj, cid), (ns_max,)
                    )
                )(consts.cids)
                if ns_pad > ns_max:
                    jitter = jnp.pad(jitter, ((0, 0), (0, ns_pad - ns_max)))
            return (
                StateArrays(
                    params, opt, arrays.hist, arrays.res, arrays.faults,
                    arrays.tel,
                ),
                jitter,
                loss,
            )

        return train_core

    def _make_comm_core(self):
        k_max, num_global = self.k_max, self.num_global
        codec, axis = self.codec, self._axis
        eaxis, ns_blk = self._eaxis, self.ns_pad // self.n_eshards
        has_stragglers = self._sched is not None and self._sched.has_stragglers
        tel = self._tel

        @scope("feds/comm")
        def comm_core(arrays, jitter, consts, do_sync, rf=None):
            fa = arrays.faults
            new_tel = arrays.tel
            rec = None
            ent = arrays.params["entity"]
            # device-side gather of shared rows; padding slots zeroed exactly
            # like RoundEngine.gather so the round functions see identical
            # inputs to the per-round engine path.  Entity-sharded, this is
            # the exact distributed gather at full Ns_pad width — the round
            # then works on this shard's slot block while the cheap per-slot
            # vectors (gid / valid / jitter) stay replicated.
            emb = eshard.dist_take_rows(ent, consts.gather_idx, eaxis)
            emb = jnp.where(consts.valid[:, :, None], emb, 0.0)
            emb = eshard.local_block(emb, eaxis, ns_blk)
            if do_sync:
                rows, pre = batched_sync_round(
                    emb, consts.gid, consts.valid,
                    num_global=num_global, axis_name=axis, entity_axis=eaxis,
                    faults=rf,
                )
                down = jnp.zeros((rows.shape[0],), jnp.int32)
                if rf is None:
                    hist = pre
                    # the full exchange transmits exact values: nothing was
                    # dropped, and stale residuals would re-inject pre-sync
                    # error into freshly-repaired rows — the bank clears
                    res = (
                        jnp.zeros_like(arrays.res)
                        if codec.has_residual else arrays.res
                    )
                else:
                    # only participating clients uploaded: their history
                    # refreshes to the pre-sync rows and their residual
                    # banks clear; absent clients keep both and recover at
                    # the next sync they attend.  The full exchange also
                    # obsoletes a present straggler's in-flight sparse
                    # messages — its queue entries are masked out.
                    sent = rf.part[:, None, None] > 0.5
                    hist = jnp.where(sent, pre, arrays.hist)
                    res = (
                        jnp.where(sent, 0.0, arrays.res)
                        if codec.has_residual else arrays.res
                    )
                    partb = rf.part > 0.5
                    fa = fa._replace(
                        age=jnp.where(partb, 0, fa.age + 1),
                        q_msk=jnp.where(partb[:, None, None], 0.0, fa.q_msk),
                    )
                if tel:
                    cl = rows.shape[0]
                    if rf is None:
                        onesf = jnp.ones((cl,), jnp.float32)
                        partf = up_okf = dn_okf = onesf
                    else:
                        partf, up_okf, dn_okf = rf.part, rf.up_ok, rf.dn_ok
                    # the full exchange bills num_shared rows on each leg for
                    # every participating client; overlap and change scores
                    # are sparse-round signals and record as zeros (the
                    # overlap carry passes through untouched — a dense
                    # exchange is not a Top-K selection)
                    billed = jnp.where(
                        partf > 0.5,
                        consts.valid.sum(axis=1).astype(jnp.int32),
                        0,
                    )
                    # health probes on the post-sync rows, full width so the
                    # divergence segment sums keep the unsharded summation
                    # order; a fault-free sync collapses div_* to exact zero
                    post_full = eshard.all_blocks(rows, eaxis)
                    div_mean, div_max = shared_divergence(
                        post_full, consts.gid, consts.valid, num_global,
                        axis_name=axis,
                    )
                    rec = RoundTelemetry(
                        up_rows=billed,
                        dn_rows=billed,
                        overlap=jnp.zeros((cl,), jnp.int32),
                        res_mass=residual_mass(res, entity_axis=eaxis),
                        part=partf,
                        up_ok=up_okf,
                        dn_ok=dn_okf,
                        age=fa.age,
                        score_hist=jnp.zeros(
                            (cl, NUM_SCORE_BUCKETS), jnp.int32
                        ),
                        div_mean=div_mean,
                        div_max=div_max,
                        upd_norm=update_norm(
                            post_full, eshard.all_blocks(emb, eaxis),
                            consts.valid,
                        ),
                        nonfinite=nonfinite_count(post_full, consts.valid),
                    )
            else:
                # halve after the f32 cast (mirrors RoundEngine.sparse_round)
                j = jnp.asarray(jitter, jnp.float32) * 0.5
                prev = (
                    (arrays.tel.prev_idx, arrays.tel.prev_msk) if tel else None
                )
                if rf is None:
                    out = batched_sparse_round(
                        emb, arrays.hist, consts.gid, consts.valid, consts.k,
                        j, k_max=k_max, num_global=num_global, codec=codec,
                        axis_name=axis, res=arrays.res, entity_axis=eaxis,
                        prev=prev,
                    )
                    rows, hist, down, res = out[:4]
                else:
                    q = (
                        (fa.q_idx, fa.q_val, fa.q_msk)
                        if has_stragglers else None
                    )
                    out = batched_sparse_round(
                        emb, arrays.hist, consts.gid, consts.valid, consts.k,
                        j, k_max=k_max, num_global=num_global, codec=codec,
                        axis_name=axis, res=arrays.res, entity_axis=eaxis,
                        faults=rf,
                        straggler=consts.straggler if has_stragglers else None,
                        queue=q,
                        prev=prev,
                    )
                    rows, hist, down, res = out[:4]
                    partb = rf.part > 0.5
                    fa = fa._replace(age=jnp.where(partb, 0, fa.age + 1))
                    if q is not None:
                        nq = out[4]
                        fa = fa._replace(
                            q_idx=nq[0], q_val=nq[1], q_msk=nq[2]
                        )
                if tel:
                    # (rec, prev') ride LAST on the round's output tuple;
                    # the engine's age field is a placeholder — the
                    # post-update staleness counters live here
                    rec, new_prev = out[-2], out[-1]
                    rec = rec._replace(age=fa.age)
                    new_tel = TelemetryArrays(
                        prev_idx=new_prev[0], prev_msk=new_prev[1]
                    )
            # the client-only outputs are identical on every entity shard
            down, fa, new_tel, rec = eshard.replicated(
                (down, fa, new_tel, rec), eaxis
            )
            rows_full = eshard.all_blocks(rows, eaxis)
            ent = eshard.scatter_rows(ent, consts.scatter_idx, rows_full, eaxis)
            params = dict(arrays.params, entity=ent)
            new_arrays = StateArrays(
                params, arrays.opt, hist, res, fa, new_tel
            )
            if tel:
                return new_arrays, down, rec
            return new_arrays, down

        return comm_core

    # ------------------------------------------------------- state plumbing
    def init_state(self, clients: Sequence["KGEClient"], seed: int = 0) -> FederationState:
        """Stack per-client params / optimizer state into padded device arrays."""
        c_n, d = self.num_clients, self.dim
        # e_pad / ns_pad == e_max / ns_max unless entity-sharded (then rows
        # are padded so the tables split into equal per-shard blocks)
        ent = np.zeros((c_n, self.e_pad, d), np.float32)
        rel = np.zeros((c_n, self.num_relations, self.rel_dim), np.float32)
        mu_e, nu_e = np.zeros_like(ent), np.zeros_like(ent)
        mu_r, nu_r = np.zeros_like(rel), np.zeros_like(rel)
        step = np.zeros((c_n,), np.int32)
        hist = np.zeros((c_n, self.ns_pad, d), np.float32)
        for c, cl in enumerate(clients):
            n = cl.model.num_entities
            ent[c, :n] = np.asarray(cl.params["entity"], np.float32)
            rel[c] = np.asarray(cl.params["relation"], np.float32)
            step[c] = int(cl.opt_state.step)
            mu_e[c, :n] = np.asarray(cl.opt_state.mu["entity"], np.float32)
            nu_e[c, :n] = np.asarray(cl.opt_state.nu["entity"], np.float32)
            mu_r[c] = np.asarray(cl.opt_state.mu["relation"], np.float32)
            nu_r[c] = np.asarray(cl.opt_state.nu["relation"], np.float32)
            v = self.views[c]
            if v.num_shared:
                hist[c, : v.num_shared] = ent[c][v.shared_local]
        if self._uniform_steps and len(set(step.tolist())) > 1:
            # the flat trainer shares one Adam step count across clients
            # (valid because equal batches-per-epoch keeps them in lockstep);
            # clients arriving with unequal counts would silently get client
            # 0's bias correction.
            raise ValueError(
                "clients have unequal Adam step counts "
                f"({step.tolist()}); the flat trainer requires lockstep steps"
            )
        arrays = StateArrays(
            params={"entity": jnp.asarray(ent), "relation": jnp.asarray(rel)},
            opt=AdamState(
                step=jnp.asarray(step),
                mu={"entity": jnp.asarray(mu_e), "relation": jnp.asarray(mu_r)},
                nu={"entity": jnp.asarray(nu_e), "relation": jnp.asarray(nu_r)},
            ),
            hist=jnp.asarray(hist),
            # error-feedback residual bank: starts all-zero (nothing dropped
            # yet); zero-width placeholder when the codec banks nothing
            res=jnp.zeros(
                (c_n, self.ns_pad if self.codec.has_residual else 0, d),
                jnp.float32,
            ),
            # staleness counters + straggler queue; zero-width queue (and a
            # pure pass-through in the programs) without an active schedule
            faults=init_fault_arrays(self._sched, c_n, self.k_max, d),
            # flight-recorder overlap carry: round 0 has no previous upload
            tel=(
                init_telemetry_arrays(c_n, self.k_max)
                if self._tel else None
            ),
        )
        return FederationState(arrays=arrays, key=jax.random.PRNGKey(seed))

    def sync_clients(self, state: FederationState, clients: Sequence["KGEClient"]) -> None:
        """Scatter the device-resident tables back into per-client params.

        The ONLY host transfer of entity tables in the device-engine paths —
        since the batched evaluator (:mod:`repro.core.evaluation`) took over
        eval boundaries, the simulation calls this exactly once, at the
        terminal best-snapshot materialization.  Optimizer state stays on
        device (clients' own opt_state is not consulted again after
        ``init_state``).
        """
        ent = np.asarray(state.arrays.params["entity"])
        rel = np.asarray(state.arrays.params["relation"])
        for c, cl in enumerate(clients):
            n = cl.model.num_entities
            cl.params = {
                "entity": jnp.asarray(ent[c, :n]),
                "relation": jnp.asarray(rel[c]),
            }

    # --------------------------------------------------------------- cycles
    @staticmethod
    def _advance(key):
        key, kb, kj = jax.random.split(key, 3)
        return key, kb, kj

    def _round_span(self, kind: str, t=None, rounds: int = 1):
        """The ``round`` telemetry span of a dispatch that trains
        ``rounds`` rounds (0 for a communication round alone)."""
        attrs = {} if t is None else {"t": t}
        return span(
            "round", kind=kind, **attrs,
            client_steps=rounds * self.client_steps,
            client_steps_real=rounds * self.client_steps_real,
            train_segments=self.train_segments if rounds else 0,
        )

    def train_cycle(self, state: FederationState):
        """``local_epochs`` of device training.  Returns (state', jitter, loss).

        Used by the ``engine="batched"`` oracle (followed by
        :meth:`comm_round`) and by the no-communication ``single`` protocol;
        the jitter output feeds the sparse round so the two-program path
        consumes the same random stream as the fused program.
        """
        with self._round_span("train"):
            key, kb, kj = self._advance(state.key)
            arrays, jitter, loss = self._train(state.arrays, kb, kj, self.consts)
        return FederationState(arrays, key), jitter, loss

    def _require_t(self, t):
        if t is None:
            raise ValueError(
                "this engine has an active FaultSchedule; communication "
                "rounds need the absolute round index t to draw the masks"
            )
        return jnp.int32(t)

    def comm_round(self, state: FederationState, jitter, sync: bool, t=None):
        """One communication round on resident state.  Returns (state', down),
        plus the round's :class:`~repro.core.telemetry.RoundTelemetry` when
        the engine was built with ``telemetry=True``.

        With an active fault schedule, ``t`` (the absolute round index) is
        required — the round's participation/drop masks are drawn from it
        inside the program.
        """
        with self._round_span("sync" if sync else "sparse", t, rounds=0):
            if self._sched is not None:
                tt = self._require_t(t)
                if sync:
                    out = self._comm_sync_f(state.arrays, self.consts, tt)
                else:
                    out = self._comm_sparse_f(
                        state.arrays, jitter, self.consts, tt
                    )
            elif sync:
                out = self._comm_sync(state.arrays, self.consts)
            else:
                out = self._comm_sparse(state.arrays, jitter, self.consts)
        if self._tel:
            arrays, down, rec = out
            return FederationState(arrays, state.key), down, rec
        arrays, down = out
        return FederationState(arrays, state.key), down

    def fused_cycle(self, state: FederationState, sync: bool, t=None):
        """One fused train+communicate cycle as a single compiled program.

        Returns ``(state', down_count (C,) device array, loss (C,))`` — the
        down counts stay on device so the caller can defer ledger accounting
        to eval boundaries — plus the round's device-resident
        :class:`~repro.core.telemetry.RoundTelemetry` when the engine was
        built with ``telemetry=True``.  ``t`` as in :meth:`comm_round`.
        """
        with self._round_span("sync" if sync else "sparse", t):
            key, kb, kj = self._advance(state.key)
            if self._sched is not None:
                fn = self._fused_sync_f if sync else self._fused_sparse_f
                out = fn(state.arrays, kb, kj, self.consts, self._require_t(t))
            else:
                fn = self._fused_sync if sync else self._fused_sparse
                out = fn(state.arrays, kb, kj, self.consts)
        if self._tel:
            arrays, down, loss, rec = out
            return FederationState(arrays, key), down, loss, rec
        arrays, down, loss = out
        return FederationState(arrays, key), down, loss


class SuperstepEngine(CycleEngine):
    """Whole ISM supersteps — ``s`` sparse rounds + 1 sync round — as ONE
    compiled program.

    :class:`CycleEngine` fused train+communicate into one program *per
    round*, but the host loop still re-entered python between rounds: one
    eager PRNG split plus one program dispatch per round, ``s+1`` times per
    ISM period.  A *superstep* ``lax.scan``-s the whole period (in general:
    any span of the round schedule, run-length-encoded by
    :func:`repro.core.sync.compress_schedule` into static ``(kind, n)``
    segments) inside a single ``jax.jit`` (host) or a single ``shard_map``
    program over the client axis (pod).  The scan carries
    ``(StateArrays, PRNG key)`` and stacks the per-round download counts and
    losses as device-side ledger accumulators, so the host touches the
    device ONCE per superstep instead of once per round.
    :meth:`superstep_with_eval` extends the plan vocabulary with ``"eval"``
    segments (:data:`repro.core.sync.PLAN_KINDS`) running the batched
    evaluator (:mod:`repro.core.evaluation`) in-program, so an ISM span AND
    its boundary eval are one dispatch returning a ``(C, 5)`` metric block.

    Equivalence contract: each scan step performs *exactly* the per-cycle
    key schedule (one 3-way ``jax.random.split``) and runs the same
    ``train_core`` / ``comm_core`` functions as :meth:`fused_cycle`, so a
    superstep over ``kinds`` is trajectory- and ledger-bitwise-identical to
    the same rounds driven one :meth:`fused_cycle` call at a time
    (tests/test_state.py property-tests this).

    Compiled programs are cached per distinct plan; with a periodic ISM
    schedule and eval-aligned supersteps only a handful of plans ever occur.
    """

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._superstep_cache: dict = {}

    # ------------------------------------------------------------ compiling
    def _compile_superstep(self, plan, eval_core=None):
        """Compile one plan into one program.

        ``plan`` is the :func:`repro.core.sync.compress_schedule` RLE of a
        span; ``("eval", n)`` segments (requiring ``eval_core``) run the
        batched evaluator's program body in place, on the state as of that
        point in the span — the program then additionally takes the
        :class:`repro.core.evaluation.EvalBank` as its last argument and
        returns the stacked ``(C, 5)`` metric blocks.
        """
        train_core = self._train_core_fn
        comm_core = self._comm_core_fn
        sched = self._sched
        tel = self._tel
        round_faults_of = self._round_faults
        has_eval = any(kind == "eval" for kind, _ in plan)
        if has_eval and eval_core is None:
            raise ValueError("plan contains eval segments but no eval_core")

        def prog(arrays, key, consts, *extra):
            # with an active fault schedule the program takes the span's
            # absolute starting round t0 right after consts and carries the
            # round index through the scan — every round (including "none"
            # rounds, which consume a round index but draw no masks)
            # advances it, eval segments do not
            if sched is not None:
                t0, eval_args = extra[0], extra[1:]
            else:
                t0, eval_args = None, extra

            def seg_step(kind):
                def step(carry, _):
                    if sched is not None:
                        arrays, key, t = carry
                    else:
                        arrays, key = carry
                    # identical key schedule to CycleEngine._advance
                    key, kb, kj = jax.random.split(key, 3)
                    arrays, jitter, loss = train_core(arrays, kb, kj, consts)
                    rf = (
                        round_faults_of(consts, t)
                        if sched is not None and kind != "none" else None
                    )
                    rec = None
                    if kind == "sync":
                        out = comm_core(
                            arrays, jitter, consts, do_sync=True, rf=rf
                        )
                        if tel:
                            arrays, down, rec = out
                        else:
                            arrays, down = out
                    elif kind == "sparse":
                        out = comm_core(
                            arrays, jitter, consts, do_sync=False, rf=rf
                        )
                        if tel:
                            arrays, down, rec = out
                        else:
                            arrays, down = out
                    else:  # "none": local training only
                        down = (loss * 0).astype(jnp.int32)
                    ys = (down, loss) if rec is None else (down, loss, rec)
                    if sched is not None:
                        return (arrays, key, t + 1), ys
                    return (arrays, key), ys

                return step

            downs, losses, recs, blocks = [], [], [], []
            carry = (
                (arrays, key, t0) if sched is not None else (arrays, key)
            )
            for kind, n in plan:
                if kind == "prefetch":
                    # host-store staging marker (repro.core.store): a pure
                    # scheduling hint consumed by the tiered driver; the
                    # device program has nothing to stage
                    continue
                if kind == "eval":
                    # in-program evaluation on the state as of this point —
                    # no state/key mutation, only the (C, 5) metric block
                    blocks.extend(
                        eval_core(carry[0].params, eval_args[0])
                        for _ in range(n)
                    )
                    continue
                # not unrolled: on TPU v5e an eval segment after an unrolled
                # segment ranked every gold triple first (MRR 1.0), though
                # the same state evaluated on its own did not
                carry, ys = jax.lax.scan(
                    seg_step(kind), carry, None, length=n
                )
                if tel and kind != "none":
                    d, l, rc = ys
                    # per-round record pytrees sliced INSIDE the program,
                    # mirroring the download counts below
                    recs.extend(
                        jax.tree.map(lambda a, i=i: a[i], rc)
                        for i in range(n)
                    )
                else:
                    d, l = ys[0], ys[1]
                if kind == "sparse":
                    # per-round (C,) rows sliced INSIDE the program, so the
                    # host never dispatches per-round slice ops
                    downs.extend(d[i] for i in range(n))
                losses.append(l)
            out = (carry[0], carry[1], tuple(downs), tuple(losses))
            if tel:
                out = out + (tuple(recs),)
            return out + (tuple(blocks),) if has_eval else out

        n_sparse = sum(n for kind, n in plan if kind == "sparse")
        n_eval = sum(n for kind, n in plan if kind == "eval")
        if self._mesh is None:
            return jax.jit(prog, donate_argnums=(0,))
        pa = self._arrays_spec()  # StateArrays-shaped (or plain prefix)
        p = jax.sharding.PartitionSpec(self._axis)
        r = jax.sharding.PartitionSpec()
        # per-segment loss stacks rounds on axis 0; clients stay on axis 1
        seg = tuple(
            jax.sharding.PartitionSpec(None, self._axis)
            for kind, _ in plan if kind not in ("eval", "prefetch")
        )
        in_specs = (pa, r, p) + ((r,) if sched is not None else ())
        in_specs = in_specs + ((self._bank_spec(),) if has_eval else ())
        out_specs = (pa, r, (p,) * n_sparse, seg)
        if tel:
            n_rec = sum(n for kind, n in plan if kind in ("sparse", "sync"))
            out_specs = out_specs + ((record_spec(p),) * n_rec,)
        if has_eval:
            out_specs = out_specs + ((p,) * n_eval,)
        return jax.jit(
            jax.shard_map(
                prog, mesh=self._mesh, in_specs=in_specs, out_specs=out_specs,
            ),
            donate_argnums=(0,),
        )

    # -------------------------------------------------------------- driving
    def superstep(self, state: FederationState, kinds: Sequence[str], t0=None):
        """Run ``len(kinds)`` rounds as one compiled program.

        ``kinds`` is the per-round ISM schedule for the span (each entry one
        of :data:`repro.core.sync.ROUND_KINDS`), e.g. a full FedS period
        ``("sparse",) * s + ("sync",)``.  Returns
        ``(state', per_round, losses)`` where ``per_round`` aligns with
        ``kinds`` as ``(kind, down_count | None)`` pairs — down counts are
        device-resident ``(C,)`` slices of the scanned accumulator, so the
        caller can defer ledger flushing to eval boundaries exactly like the
        per-cycle path — and ``losses`` is one ``(n, C)`` device array per
        plan segment.
        """
        plan = compress_schedule(kinds)
        if any(kind == "eval" for kind, _ in plan):
            raise ValueError(
                "superstep() takes round kinds only; use superstep_with_eval "
                "to fold an eval segment into the program"
            )
        fn = self._superstep_cache.get(plan)
        if fn is None:
            fn = self._superstep_cache[plan] = self._compile_superstep(plan)
        args = (state.arrays, state.key, self.consts)
        if self._sched is not None:
            args = args + (self._require_t(t0),)
        with self._round_span("superstep", t0, rounds=len(kinds)):
            out = fn(*args)
        if self._tel:
            arrays, key, downs, losses, recs = out
            per_round = self._align(kinds, downs, recs)
        else:
            arrays, key, downs, losses = out
            per_round = self._align(kinds, downs)
        return FederationState(arrays, key), per_round, losses

    def superstep_with_eval(
        self,
        state: FederationState,
        kinds: Sequence[str],
        evaluator,  # repro.core.evaluation.BatchedEvaluator
        split: str = "valid",
        t0=None,
    ):
        """Run ``len(kinds)`` rounds PLUS the boundary evaluation as one
        compiled program.

        The plan is ``kinds`` with an ``"eval"`` segment appended
        (:data:`repro.core.sync.PLAN_KINDS`), so the filtered-ranking eval
        of :class:`repro.core.evaluation.BatchedEvaluator` runs on-device
        inside the same scanned program as the rounds — the host never
        syncs entity tables at the boundary, it reads back one ``(C, 5)``
        metric block.  Returns ``(state', per_round, losses, block)`` with
        the first three exactly as :meth:`superstep`.
        """
        plan = compress_schedule(tuple(kinds) + ("eval",))
        # the evaluator is part of the key: its eval_core closes over
        # method/gamma/chunk, so two evaluators sharing a plan+split must
        # not reuse each other's compiled program
        cache_key = (plan, split, evaluator)
        fn = self._superstep_cache.get(cache_key)
        if fn is None:
            fn = self._superstep_cache[cache_key] = self._compile_superstep(
                plan, eval_core=evaluator.eval_core
            )
        args = (state.arrays, state.key, self.consts)
        if self._sched is not None:
            args = args + (self._require_t(t0),)
        with self._round_span("superstep", t0, rounds=len(kinds)):
            out = fn(*args, evaluator.banks[split])
        if self._tel:
            arrays, key, downs, losses, recs, blocks = out
            per_round = self._align(kinds, downs, recs)
        else:
            arrays, key, downs, losses, blocks = out
            per_round = self._align(kinds, downs)
        return (
            FederationState(arrays, key),
            per_round,
            losses,
            blocks[0],
        )

    @staticmethod
    def _align(kinds, downs, recs=None):
        """Zip per-round kinds with their device-resident download counts.

        Without telemetry: ``(kind, down | None)`` pairs, as always.  With
        telemetry (``recs`` given): ``(kind, down | None, rec | None)``
        triples — comm rounds carry their :class:`RoundTelemetry`, ``"none"``
        rounds carry ``None``.
        """
        down_iter = iter(downs)
        if recs is None:
            return [
                (kind, next(down_iter) if kind == "sparse" else None)
                for kind in kinds
            ]
        rec_iter = iter(recs)
        return [
            (
                kind,
                next(down_iter) if kind == "sparse" else None,
                next(rec_iter) if kind != "none" else None,
            )
            for kind in kinds
        ]
