"""The federated training simulation loop (paper §IV experimental protocol).

A thin host loop: it owns the communication ledger, eval scheduling, and
best-snapshot logic — everything else runs on device.  Four engines drive
the per-round work (``FederatedConfig.engine``):

* ``superstep`` — whole spans of the ISM round schedule (``s`` sparse
  rounds + 1 sync round per period, chunked to eval boundaries) run as ONE
  ``lax.scan``-ned program per superstep *including the boundary eval*
  (:class:`repro.core.state.SuperstepEngine` ``"eval"`` plan segments over
  :class:`repro.core.evaluation.BatchedEvaluator`): one host touch-point
  per superstep instead of one per round.  Fastest path; compiles one
  program per distinct schedule plan.
* ``fused`` (default) — the whole cycle (``local_epochs`` of local training with
  device-pre-sampled batches + the FedS communication round) is ONE
  compiled program per round over :class:`repro.core.state.FederationState`,
  which keeps every client's entity/relation tables, Adam state, upload
  history, and the jitter PRNG key device-resident across rounds.
* ``batched`` — the same device-resident state and random streams, but the
  training scan and the communication round run as separate jitted programs
  per round.  This is the correctness oracle for ``fused`` (same seeds ->
  same eval trajectory and ledger totals, see tests/test_state.py).
* ``reference`` — the ragged numpy host protocol (per-client
  ``KGEClient.train_local`` + :mod:`repro.core.aggregate`), the
  paper-faithful path the engine property tests compare against.

All device engines produce bit-identical trajectories and ledgers for the
same config/seeds — they differ only in how many rounds each compiled
program covers (the fused==batched==superstep equivalence contract,
property-tested in tests/test_state.py; see docs/architecture.md).

Pod mode: ``mesh_devices > 1`` builds a 1-D client-axis mesh via
:func:`repro.launch.mesh.make_federation_mesh` and runs the same engine
programs under ``shard_map`` with the client axis sharded over devices.

Ledger accounting for the device engines is deferred: per-round download
counts stay on device and are flushed to the :class:`CommLedger` only at
eval boundaries (one transfer for all pending rounds), producing bitwise-
identical totals to per-round flushing.  Wire payloads and their cost
accounting go through the pluggable codec registry
(:mod:`repro.core.codecs`, selected by ``FederatedConfig.codec`` spec
strings like ``"int8:ef=1"``); error-feedback codecs carry device-resident
residual state inside :class:`repro.core.state.FederationState` on the
device engines, and host-side numpy banks
(:func:`repro.core.protocol.sparse_upload_coded`) on the ``reference``
path.

Evaluation on the device engines is itself device-resident
(:mod:`repro.core.evaluation`): boundaries read back only a ``(C, 5)``
``[mrr, hits@1, hits@3, hits@10, count]`` block, best-model snapshots are on-device params
copies taken when MRR improves, and entity tables cross the host exactly
once — at the terminal snapshot materialization.  A terminal eval boundary
is guaranteed even when ``rounds % eval_every != 0``.  The ``reference``
engine keeps the per-client host oracle (``KGEClient.evaluate``) the
device path is property-tested exactly equal to.
"""
from __future__ import annotations

import collections
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry
from repro.core.aggregate import Upload, fede_aggregate, personalized_aggregate
from repro.core.codecs import parse_codec_spec
from repro.core.evaluation import BatchedEvaluator
from repro.core.faults import host_round_faults, parse_fault_spec
from repro.core.health import HealthMonitor, parse_alert_spec
from repro.core.protocol import (
    apply_full_download,
    apply_sparse_download,
    build_comm_views,
    full_upload,
    sparse_upload_coded,
)
from repro.core.sparsify import change_scores, sparsity_k
from repro.core.state import CycleEngine, FederationState, SuperstepEngine
from repro.core.store import TieredCycleEngine
from repro.core.telemetry import (
    NUM_SCORE_BUCKETS,
    RoundTelemetry,
    TelemetrySink,
    nonfinite_count,
    residual_mass,
    score_histogram,
    shared_divergence,
    update_norm,
)
from repro.core.sync import round_kind
from repro.data.partition import ClientData
from repro.federated import checkpoint as fed_checkpoint
from repro.federated.client import KGEClient
from repro.federated.comm import CommLedger
from repro.federated.metrics import aggregate_eval_block, weighted_average
from repro.launch.mesh import make_federation_mesh

ENGINES = ("fused", "batched", "reference", "superstep", "tiered")


@dataclasses.dataclass
class FederatedConfig:
    method: str = "transe"  # any registered scoring method (kge.scoring)
    protocol: str = "feds"  # single | fedep | feds | feds_nosync
    dim: int = 256
    rounds: int = 200
    local_epochs: int = 3
    batch_size: int = 512
    num_negatives: int = 64
    lr: float = 1e-4
    adversarial_temperature: float = 1.0
    gamma: float = 8.0
    sparsity_p: float = 0.4
    # wire-codec spec "name:key=val,..." (repro.core.codecs registry), e.g.
    # "int8:ef=1" or "lowrank:cols=8,rank=2" — error-feedback (ef) codecs
    # carry device-resident residual state and need a device engine
    codec: str = "identity"
    quantize_upload: bool = False  # legacy alias for codec="int8" (FedS+Q8)
    # fused (one program per cycle) | superstep (one program per ISM span)
    # | batched (per-round programs, oracle) | reference (ragged numpy host)
    engine: str = "fused"
    # >1: pod mode — shard the client axis over a 1-D device mesh
    # (launch/mesh.py); requires a device engine and C % mesh_devices == 0
    mesh_devices: int = 0
    # >1: entity-sharded pod mode — a 2-D (clients, entities) mesh; the
    # padded entity/hist/residual state and the eval candidate scan shard
    # over the entity axis so per-device memory scales as E_pad / shards.
    # Bitwise identical to the unsharded engines (tests/test_eshard*.py).
    # Total devices used = max(mesh_devices, 1) * mesh_entities.
    mesh_entities: int = 0
    # host-tiered embedding store (engine="tiered", or host_store=True as an
    # alias): the device holds only the pinned shared prefix plus a
    # temperature/LRU row cache — E_max becomes a config value instead of a
    # device-memory obligation.  Training is lockstep (clients' train sets
    # are truncated to the common minimum) and uses sparse-Adam segment
    # semantics, so trajectories are NOT bitwise equal to the dense engines
    # (they ARE bitwise invariant to cache_slots — see tests/test_store.py).
    host_store: bool = False
    cache_slots: int = 0  # 0 -> floor: exactly the working-view width W
    stage_steps: int = 0  # batches per staging segment; 0 -> whole epoch
    sync_interval: int = 4
    eval_every: int = 5
    patience: int = 3
    max_eval_triples: int = 500
    seed: int = 0
    # fault-injection spec (repro.core.faults grammar), e.g.
    # "p=0.5,drop_up=0.1,stragglers=0:2,lag=2,seed=7"; "" -> fully reliable
    # federation (trivial schedules compile the exact pre-fault programs)
    faults: str = ""
    # host-loop durability: write a full resume image (state + ledger + eval
    # bookkeeping, repro.federated.checkpoint) at the first eval boundary at
    # least checkpoint_every rounds after the last write; resume=True
    # restores it and continues the interrupted run bitwise
    checkpoint_path: str = ""
    checkpoint_every: int = 0
    resume: bool = False
    # flight recorder: JSONL event path ("" -> off).  On: the engines carry
    # per-round on-device records (repro.core.telemetry) drained at eval
    # boundaries, host stages are timed as spans, and a shadow ledger replays
    # the records to cross-check the real accounting (tools/trace_report.py).
    # Off: zero-cost — the engines compile the exact pre-telemetry programs.
    telemetry: str = ""
    # streaming health monitor: alert-rule spec (repro.core.health grammar),
    # e.g. "divergence>0.5;nan;mrr-stall=20;byte-budget=2e9"; requires
    # telemetry (rules judge the drained event stream).  alert_mode "warn"
    # records ``alert`` events only; "fail" additionally stops the run
    # gracefully at the next eval boundary after an alert fires (the stream
    # still ends with the terminal ledger event).
    alerts: str = ""
    alert_mode: str = "warn"


@dataclasses.dataclass
class FederatedResult:
    config: FederatedConfig
    eval_history: list  # [(round, val_mrr, val_hits10)]
    ledger: CommLedger
    best_round: int
    val_mrr_cg: float  # validation MRR at convergence (best round)
    test_mrr_cg: float
    test_hits10_cg: float
    rounds_run: int

    def params_at(self, round_idx: int) -> float:
        return self.ledger.params_at_round(round_idx)


def _empty_upload(client_id: int, dim: int) -> Upload:
    """A zero-entity message: a queue vacancy / an undelivered upload."""
    return Upload(
        client_id=client_id,
        entity_ids=np.zeros(0, dtype=np.int64),
        values=np.zeros((0, dim), dtype=np.float32),
    )


def _snapshot(clients: list[KGEClient]):
    return [
        {k: np.asarray(v) for k, v in c.params.items()} for c in clients
    ]


def _restore(clients: list[KGEClient], snap) -> None:
    for c, s in zip(clients, snap):
        c.params = {k: jnp.asarray(v) for k, v in s.items()}


def _flush_ledger(
    ledger, pending, views, codec, dim, k_per_client, sched=None,
    sink=None, cache_stats=None,
) -> None:
    """Replay deferred rounds into the ledger.

    ``pending`` holds ``(kind, down_count, round_idx, record)`` per round in
    order; sparse-round download counts (and, with telemetry on, the
    :class:`~repro.core.telemetry.RoundTelemetry` records) are device
    arrays, pulled to host in ONE transfer here.  The replay performs the
    exact same accounting-call sequence a per-round flush would, so ledger
    totals/history are bitwise identical.

    With an active fault schedule ``sched``, the per-round participation
    masks are re-drawn on host from the absolute round index (bit-identical
    to the in-program draws, :func:`repro.core.faults.host_round_faults`)
    and absent clients are *skipped entirely* — a non-participating client
    exchanges no bytes, not zero-entity messages (whose sign bitmaps would
    still bill ``Ns`` bytes).  Delivery drops do NOT reduce billing: a
    dropped message was still transmitted.

    With a ``sink``, each drained record is emitted as a ``round`` event and
    replayed into the sink's *shadow* ledger using only device-recorded
    quantities — the reconciliation cross-check trace_report verifies.
    ``cache_stats`` (tiered engine) is a per-pending-round list of cache
    hit/miss/eviction deltas folded into the events.
    """
    with telemetry.span("ledger_flush", rounds=len(pending)):
        sparse_counts = [d for kind, d, _, _ in pending if kind == "sparse"]
        dc_all = np.asarray(jnp.stack(sparse_counts)) if sparse_counts else None
        recs = [r for _, _, _, r in pending if r is not None]
        stacked = (
            jax.tree.map(lambda *xs: np.asarray(jnp.stack(xs)), *recs)
            if recs else None
        )
        i = 0
        j = 0
        for n, (kind, _, t, rec) in enumerate(pending):
            part = (
                host_round_faults(sched, t, len(views))[0]
                if sched is not None else None
            )
            if kind == "sync":
                for v in views:  # upload leg + download leg
                    if part is not None and not part[v.client_id]:
                        continue
                    ledger.log_full_exchange(v.num_shared, dim)
                    ledger.log_full_exchange(v.num_shared, dim)
            elif kind == "sparse":
                for v, k_c, dc in zip(views, k_per_client, dc_all[i]):
                    if part is not None and not part[v.client_id]:
                        continue
                    codec.log_upload(ledger, int(k_c), dim, v.num_shared)
                    codec.log_download(ledger, int(dc), dim, v.num_shared)
                i += 1
            ledger.end_round()
            if sink is not None:
                r = None
                if rec is not None:
                    r = jax.tree.map(lambda a, j=j: a[j], stacked)
                    j += 1
                _emit_round_event(
                    sink, codec, dim, views, kind, t, r,
                    cache=cache_stats[n] if cache_stats else None,
                )
        pending.clear()
        if cache_stats is not None:
            cache_stats.clear()


def _emit_round_event(sink, codec, dim, views, kind, t, rec, cache=None):
    """Emit one ``{"ev": "round"}`` event and feed the shadow ledger.

    The shadow replay makes the SAME accounting calls, in the same order,
    as the real flush just did — but parameterized only by device-recorded
    quantities (``rec.up_rows``/``dn_rows``/``part``), never by the host's
    own bookkeeping.  If the records are faithful, shadow totals equal the
    real ledger's bitwise (all per-call increments are integer-valued, so
    float accumulation is exact); trace_report asserts exactly that.
    Per-leg wire bytes are measured as shadow-ledger deltas around each
    call.  ``rec=None`` (a no-comm round) still advances the shadow round
    counter, mirroring ``ledger.end_round()``.
    """
    shadow = sink.shadow
    c_n = len(views)
    if rec is None:
        shadow.end_round()
        zi = [0] * c_n
        sink.emit({
            "ev": "round", "round": int(t), "kind": kind,
            "up_rows": zi, "dn_rows": zi, "overlap": zi,
            "res_mass": [0.0] * c_n, "part": zi, "up_ok": zi, "dn_ok": zi,
            "age": zi,
            "score_hist": [[0] * NUM_SCORE_BUCKETS for _ in range(c_n)],
            "div_mean": [0.0] * c_n, "div_max": [0.0] * c_n,
            "upd_norm": [0.0] * c_n, "nonfinite": zi,
            "up_bytes": [0.0] * c_n, "dn_bytes": [0.0] * c_n,
            "cache_hits": int(cache["hits"]) if cache else 0,
            "cache_misses": int(cache["misses"]) if cache else 0,
            "cache_evictions": int(cache["evictions"]) if cache else 0,
            "cum_params": shadow.params_transmitted,
            "cum_bytes": shadow.bytes_int8_signs,
        })
        return
    up_bytes, dn_bytes = [], []
    for v in views:
        c = v.client_id
        if rec.part[c] <= 0.5:
            up_bytes.append(0.0)
            dn_bytes.append(0.0)
            continue
        b0 = shadow.bytes_int8_signs
        if kind == "sync":
            shadow.log_full_exchange(int(rec.up_rows[c]), dim)
            b1 = shadow.bytes_int8_signs
            shadow.log_full_exchange(int(rec.dn_rows[c]), dim)
        else:
            codec.log_upload(shadow, int(rec.up_rows[c]), dim, v.num_shared)
            b1 = shadow.bytes_int8_signs
            codec.log_download(shadow, int(rec.dn_rows[c]), dim, v.num_shared)
        up_bytes.append(b1 - b0)
        dn_bytes.append(shadow.bytes_int8_signs - b1)
    shadow.end_round()
    sink.emit({
        "ev": "round", "round": int(t), "kind": kind,
        "up_rows": [int(x) for x in rec.up_rows],
        "dn_rows": [int(x) for x in rec.dn_rows],
        "overlap": [int(x) for x in rec.overlap],
        "res_mass": [float(x) for x in rec.res_mass],
        "part": [int(x > 0.5) for x in rec.part],
        "up_ok": [int(x > 0.5) for x in rec.up_ok],
        "dn_ok": [int(x > 0.5) for x in rec.dn_ok],
        "age": [int(x) for x in rec.age],
        "score_hist": [[int(x) for x in row] for row in rec.score_hist],
        "div_mean": [float(x) for x in rec.div_mean],
        "div_max": [float(x) for x in rec.div_max],
        "upd_norm": [float(x) for x in rec.upd_norm],
        "nonfinite": [int(x) for x in rec.nonfinite],
        "up_bytes": up_bytes, "dn_bytes": dn_bytes,
        "cache_hits": int(cache["hits"]) if cache else 0,
        "cache_misses": int(cache["misses"]) if cache else 0,
        "cache_evictions": int(cache["evictions"]) if cache else 0,
        "cum_params": shadow.params_transmitted,
        "cum_bytes": shadow.bytes_int8_signs,
    })


def _emit_ledger_event(sink, ledger) -> None:
    """The terminal reconciliation event: real vs shadow ledger totals."""
    sh = sink.shadow
    sink.emit({
        "ev": "ledger",
        "params_transmitted": ledger.params_transmitted,
        "bytes": ledger.bytes_int8_signs,
        "rounds": ledger.rounds,
        "shadow_params": sh.params_transmitted,
        "shadow_bytes": sh.bytes_int8_signs,
        "shadow_rounds": sh.rounds,
        "reconciled": bool(
            ledger.params_transmitted == sh.params_transmitted
            and ledger.bytes_int8_signs == sh.bytes_int8_signs
            and ledger.rounds == sh.rounds
        ),
    })


def run_federated(
    clients_data: list[ClientData],
    num_global_entities: int,
    cfg: FederatedConfig,
    verbose: bool = False,
) -> FederatedResult:
    if cfg.engine not in ENGINES:
        raise ValueError(
            f"unknown engine {cfg.engine!r}; expected one of {ENGINES}"
        )
    rules = parse_alert_spec(cfg.alerts)  # eager: bad specs fail before work
    if rules and not cfg.telemetry:
        raise ValueError(
            "alerts need the event stream: set telemetry=<path> "
            "(--telemetry) alongside alerts"
        )
    if not cfg.telemetry:
        return _run_federated_impl(
            clients_data, num_global_entities, cfg, verbose, None
        )
    sink = TelemetrySink(cfg.telemetry)
    # the shadow ledger: re-bills every round from device-recorded telemetry
    # only; _finish's "ledger" event compares it to the real one bitwise
    sink.shadow = CommLedger()
    if rules:
        sink.monitor = HealthMonitor(rules, mode=cfg.alert_mode)
    sink.emit({
        "ev": "run",
        "engine": (
            "tiered" if (cfg.host_store or cfg.engine == "tiered")
            else cfg.engine
        ),
        "codec": "int8" if cfg.quantize_upload else cfg.codec,
        "method": cfg.method,
        "protocol": cfg.protocol,
        "clients": len(clients_data),
        "dim": cfg.dim,
        "rounds": cfg.rounds,
        "telemetry_version": 1,
    })
    try:
        with telemetry.session(sink):
            return _run_federated_impl(
                clients_data, num_global_entities, cfg, verbose, sink
            )
    finally:
        sink.close()


def _run_federated_impl(
    clients_data: list[ClientData],
    num_global_entities: int,
    cfg: FederatedConfig,
    verbose: bool,
    sink,
) -> FederatedResult:
    sched = parse_fault_spec(cfg.faults)
    faulted = not sched.trivial
    checkpointing = bool(cfg.checkpoint_path)
    if cfg.checkpoint_every and not checkpointing:
        raise ValueError("checkpoint_every set without checkpoint_path")
    if cfg.resume and not checkpointing:
        raise ValueError("resume=True needs checkpoint_path")
    if cfg.host_store or cfg.engine == "tiered":
        if faulted:
            raise ValueError(
                "the host-tiered engine does not support fault schedules; "
                "use a dense device engine (fused/batched/superstep) or "
                "engine='reference'"
            )
        if checkpointing:
            raise ValueError(
                "checkpointing is implemented for the dense device engines "
                "only, not the host-tiered engine"
            )
        if cfg.mesh_devices > 1 or cfg.mesh_entities > 1:
            raise ValueError(
                "the host-tiered engine is a host-loop path; it composes "
                "with neither mesh_devices nor mesh_entities"
            )
        if cfg.engine not in ("tiered", "fused"):
            raise ValueError(
                f"host_store=True selects engine='tiered'; it conflicts "
                f"with engine={cfg.engine!r}"
            )
        return _run_federated_tiered(
            clients_data, num_global_entities, cfg, verbose, sink
        )
    clients = [
        KGEClient(
            d,
            method=cfg.method,
            dim=cfg.dim,
            gamma=cfg.gamma,
            batch_size=cfg.batch_size,
            num_negatives=cfg.num_negatives,
            lr=cfg.lr,
            adversarial_temperature=cfg.adversarial_temperature,
            seed=cfg.seed,
        )
        for d in clients_data
    ]
    views = build_comm_views(
        [d.local_to_global for d in clients_data], num_global_entities
    )
    codec_spec = cfg.codec
    if cfg.quantize_upload:
        if codec_spec not in ("identity", "int8", "int8-rows"):
            raise ValueError(
                f"quantize_upload (legacy alias for codec='int8') conflicts "
                f"with codec={cfg.codec!r}; set one of the two"
            )
        codec_spec = "int8"
    codec = parse_codec_spec(codec_spec)
    ledger = CommLedger()

    use_device = cfg.engine != "reference"
    if checkpointing and not use_device:
        raise ValueError(
            "checkpointing needs a device engine (the reference path keeps "
            "ragged host state with no stable serialization)"
        )
    sched.validate_clients(len(clients))
    mesh = None
    entity_axis = None
    if cfg.mesh_devices > 1 or cfg.mesh_entities > 1:
        if not use_device:
            raise ValueError(
                "pod mode (mesh_devices/mesh_entities > 1) requires a "
                "device engine, not engine='reference'"
            )
        if cfg.mesh_entities > 1 and jax.default_backend() == "tpu":
            raise ValueError(
                "mesh_entities > 1 is refused on TPU: on TPU v5e the "
                "entity-sharded engine corrupts the relation tables' Adam "
                "state in its first round and trains to NaN (ROADMAP B0). "
                "Use mesh_entities=1 until `chip_smoke.py --four-chips` "
                "passes."
            )
        mesh = make_federation_mesh(
            max(cfg.mesh_devices, 1),
            entity_devices=max(cfg.mesh_entities, 1),
        )
        entity_axis = "entities" if cfg.mesh_entities > 1 else None
    evaluator = None
    if use_device:
        engine_cls = SuperstepEngine if cfg.engine == "superstep" else CycleEngine
        cycle = engine_cls(
            clients, views, num_global_entities,
            sparsity_p=cfg.sparsity_p, local_epochs=cfg.local_epochs,
            codec=codec, mesh=mesh, entity_axis=entity_axis,
            faults=sched, telemetry=sink is not None,
        )
        state = cycle.init_state(clients, seed=cfg.seed + 777)
        # (kind, device down_count | None, round, record | None) 4-tuples
        pending: list = []
        # device-resident batched eval: banks built ONCE, eval boundaries
        # read back only a (C, EVAL_BLOCK_COLS) scalar block (no
        # sync_clients round-trip)
        evaluator = BatchedEvaluator(
            clients_data, method=cfg.method, gamma=cfg.gamma,
            e_max=cycle.e_max, max_triples=cfg.max_eval_triples,
            splits=("valid", "test"),
            known=[c._known for c in clients], mesh=mesh,
            entity_axis=entity_axis,
        )
    else:  # ragged numpy reference protocol keeps per-client histories
        rng = np.random.default_rng(cfg.seed + 777)
        histories = [
            clients[c].entity_embeddings[jnp.asarray(views[c].shared_local)]
            for c in range(len(clients))
        ]
        # host-side error-feedback banks (the ef=1 paper-faithful oracle)
        residuals = [
            np.zeros((v.num_shared, cfg.dim), np.float32) for v in views
        ] if codec.has_residual else None
        # straggler in-flight queues (host twin of FaultArrays.q_*): one
        # FIFO of lag messages per straggler, initialized empty — the first
        # lag contributions of a straggler are nothing at all
        straggler_q = {
            c: collections.deque(
                _empty_upload(c, cfg.dim) for _ in range(sched.lag)
            )
            for c in sched.stragglers
        } if (faulted and sched.has_stragglers) else None
        # telemetry host twins: the reference path has no device records, so
        # it rebuilds them from the ragged host state — through the SAME jit
        # helpers on identically padded buffers, so wherever the trajectory
        # matches the device engines bitwise, the records do too
        if sink is not None:
            tel_ns_max = max(v.num_shared for v in views)
            tel_nsv = np.array([v.num_shared for v in views])
            tel_valid = jnp.asarray(
                np.arange(tel_ns_max)[None, :] < tel_nsv[:, None]
            )
            tel_prev = [set() for _ in clients]  # last SENT upload, per client
            tel_ages = np.zeros(len(clients), np.int32)
            # padded gid twin of build_padded_views (padding -> num_global,
            # the throwaway divergence segment)
            tel_gid_np = np.full(
                (len(clients), tel_ns_max), num_global_entities, np.int32
            )
            for v in views:
                tel_gid_np[v.client_id, : v.num_shared] = v.shared_global
            tel_gid = jnp.asarray(tel_gid_np)

            def _tel_rows_pad():
                """Clients' current shared rows, padded like the engines'."""
                pad = np.zeros(
                    (len(clients), tel_ns_max, cfg.dim), np.float32
                )
                for c, v in zip(clients, views):
                    pad[v.client_id, : v.num_shared] = np.asarray(
                        c.params["entity"]
                    )[v.shared_local]
                return pad

    eval_history: list[tuple[int, float, float]] = []
    best = {"mrr": -1.0, "round": 0, "snap": None, "hits": 0.0}
    declines = 0
    prev_mrr = -1.0
    rounds_run = 0
    start_round = 0
    last_ckpt = 0
    # the "single" baseline evaluates on a slower cadence (no comm cost to track)
    ee = max(cfg.eval_every, 10) if cfg.protocol == "single" else cfg.eval_every

    if cfg.resume and os.path.exists(cfg.checkpoint_path):
        # bitwise continuation: everything trajectory-determining lives in
        # the restored FederationState (tables, Adam, hist, EF residuals,
        # fault arrays, PRNG key) + these host loop variables; fault masks
        # are drawn from the absolute round index, so nothing else is needed
        state, loop = fed_checkpoint.load_checkpoint(
            cfg.checkpoint_path, state, ledger, cfg=cfg
        )
        start_round = last_ckpt = loop["next_round"]
        eval_history = loop["eval_history"]
        best = loop["best"]
        declines = loop["declines"]
        prev_mrr = loop["prev_mrr"]
        rounds_run = start_round
        if verbose:
            print(
                f"resumed from {cfg.checkpoint_path} at round {start_round}"
            )

    def eval_boundary(round_no: int, block=None) -> bool:
        """Flush+evaluate at ``round_no``; True => early-stop.

        Device engines evaluate on device: ``block`` is the evaluator's
        ``(C, 5)`` metric block when the superstep program already produced
        it in-program, else the standalone compiled evaluator runs here —
        either way no entity table crosses the host, and the best-model
        snapshot is a cheap on-device copy taken only when MRR improves.
        """
        nonlocal best, declines, prev_mrr, last_ckpt
        if use_device:
            _flush_ledger(
                ledger, pending, views, codec, cfg.dim, cycle.k_per_client,
                sched=sched if faulted else None, sink=sink,
            )
            if block is None:
                block = evaluator.evaluate(state.arrays.params, "valid")
            val = aggregate_eval_block(block)
        else:
            val = weighted_average(
                [c.evaluate("valid", cfg.max_eval_triples) for c in clients]
            )
        if sink is not None:
            sink.emit({
                "ev": "eval", "round": int(round_no), "split": "valid",
                "mrr": float(val["mrr"]), "hits10": float(val["hits10"]),
                "params_transmitted": ledger.params_transmitted,
                "bytes": ledger.bytes_int8_signs,
            })
        eval_history.append((round_no, val["mrr"], val["hits10"]))
        if verbose:
            print(
                f"round {round_no:4d}  val MRR {val['mrr']:.4f}  "
                f"Hits@10 {val['hits10']:.4f}  params {ledger.params_transmitted:.3e}"
            )
        if val["mrr"] > best["mrr"]:
            snap = (
                {k: jnp.copy(v) for k, v in state.arrays.params.items()}
                if use_device else _snapshot(clients)
            )
            best = {
                "mrr": val["mrr"],
                "round": round_no,
                "snap": snap,
                "hits": val["hits10"],
            }
        declines = declines + 1 if val["mrr"] < prev_mrr else 0
        prev_mrr = val["mrr"]
        if (
            checkpointing
            and cfg.checkpoint_every > 0
            and round_no - last_ckpt >= cfg.checkpoint_every
        ):
            # eval boundaries are the device engines' only host touch-points,
            # so they are the checkpoint cadence too; the ledger was just
            # flushed, so pending is empty and the image is self-contained
            fed_checkpoint.save_checkpoint(
                cfg.checkpoint_path, state, ledger, cfg=cfg,
                next_round=round_no, eval_history=eval_history, best=best,
                declines=declines, prev_mrr=prev_mrr,
            )
            last_ckpt = round_no
        if sink is not None and sink.monitor is not None \
                and sink.monitor.should_stop():
            # fail-fast alert mode: stop gracefully — _finish still runs,
            # so the stream keeps its terminal ledger event
            if verbose:
                print(f"round {round_no:4d}  stopping on fail-level alert")
            return True
        return declines >= cfg.patience

    if cfg.engine == "superstep":
        # ------------------- superstep mode: chunk rounds to eval boundaries
        # so every superstep runs as ONE compiled program INCLUDING its
        # boundary eval (an "eval" plan segment), and evals land at exactly
        # the same rounds as the per-round engines.  Chunks end either at an
        # eval boundary or at the final round (terminal eval guarantee), so
        # every chunk carries an eval segment.
        t = start_round
        while t < cfg.rounds:
            chunk = min(((t // ee) + 1) * ee, cfg.rounds) - t
            kinds = tuple(
                round_kind(u, cfg.protocol, cfg.sync_interval)
                for u in range(t, t + chunk)
            )
            state, per_round, _losses, block = cycle.superstep_with_eval(
                state, kinds, evaluator, "valid", t0=t
            )
            if sink is None:
                pending.extend(
                    (k, d, t + i, None) for i, (k, d) in enumerate(per_round)
                )
            else:  # with telemetry the engine aligns (kind, down, record)
                pending.extend(
                    (k, d, t + i, r)
                    for i, (k, d, r) in enumerate(per_round)
                )
            t += chunk
            rounds_run = t
            if eval_boundary(t, block=block):
                break
        # superstep is always a device engine, so cycle/state/pending exist
        return _finish(
            cfg, clients, use_device, cycle, state, pending,
            views, codec, ledger, eval_history, best, rounds_run, evaluator,
            sched=sched if faulted else None, sink=sink,
        )

    for t in range(start_round, cfg.rounds):
        rounds_run = t + 1
        kind = round_kind(t, cfg.protocol, cfg.sync_interval)
        comm = kind != "none"
        sync = kind == "sync"

        if use_device:
            # ------------------------- device-resident train+communicate
            rec = None
            if cfg.engine == "fused":
                if comm:
                    out = cycle.fused_cycle(state, sync=sync, t=t)
                    if sink is not None:
                        state, down, _loss, rec = out
                    else:
                        state, down, _loss = out
                else:
                    state, _jitter, _loss = cycle.train_cycle(state)
                    down = None
            else:  # per-round oracle: separate train / comm programs
                state, jitter, _loss = cycle.train_cycle(state)
                down = None
                if comm:
                    out = cycle.comm_round(state, jitter, sync=sync, t=t)
                    if sink is not None:
                        state, down, rec = out
                    else:
                        state, down = out
            pending.append((kind, down if kind == "sparse" else None, t, rec))
        else:
            # ----------------------------------- numpy reference protocol
            # fault semantics (repro.core.faults): part -> the client
            # computes its upload (history / EF refresh) and exchanges bytes;
            # part & up_ok -> the message reaches the server (enters Eq. 3);
            # part & dn_ok -> the download lands (Eq. 4 applies).  Local
            # training is never gated — an absent client trains on, it just
            # doesn't communicate (matching the device engines' ungated
            # train scan).
            for c in clients:
                c.train_local(cfg.local_epochs)
            if faulted and comm:
                fpart, fup, fdn = host_round_faults(sched, t, len(clients))
            else:
                fpart = fup = fdn = np.ones(len(clients), dtype=bool)
            tel_pre = None
            if comm and sync:
                if sink is not None:
                    # pre-round shared rows, for the update-norm probe twin
                    tel_pre = _tel_rows_pad()
                uploads = []
                for c, v in zip(clients, views):
                    if not fpart[v.client_id]:
                        continue
                    if residuals is not None:
                        # the full exchange transmits exact values: stale
                        # banked error would re-inject pre-sync loss (same
                        # contract as the device engines' residual clear)
                        residuals[v.client_id][:] = 0.0
                    up, hist = full_upload(c.params["entity"], v)
                    histories[v.client_id] = hist
                    if straggler_q is not None and v.client_id in straggler_q:
                        # the full exchange obsoletes in-flight sparse
                        # messages — a present straggler's queue empties
                        # (the ISM sync round doubles as a recovery point)
                        straggler_q[v.client_id] = collections.deque(
                            _empty_upload(v.client_id, cfg.dim)
                            for _ in range(sched.lag)
                        )
                    if fup[v.client_id]:
                        uploads.append(up)
                    ledger.log_full_exchange(v.num_shared, cfg.dim)
                if uploads:
                    global_mean, count = fede_aggregate(
                        uploads, num_global_entities
                    )
                for c, v in zip(clients, views):
                    if not fpart[v.client_id]:
                        continue
                    if uploads and fdn[v.client_id]:
                        # count-guarded: entities nobody uploaded this round
                        # keep their local rows (zero-participant guard)
                        c.params["entity"] = apply_full_download(
                            c.params["entity"], v, global_mean, count=count
                        )
                    ledger.log_full_exchange(v.num_shared, cfg.dim)
            elif comm:  # sparse FedS round, ragged numpy reference path
                if sink is not None:
                    # the device records score changes on post-train
                    # embeddings vs PRE-round histories — snapshot both
                    # before the upload loop refreshes them, padded to the
                    # same (C, Ns_max, D) the engines scan
                    emb_pad = np.zeros(
                        (len(clients), tel_ns_max, cfg.dim), np.float32
                    )
                    hist_pad = np.zeros_like(emb_pad)
                    for c, v in zip(clients, views):
                        n = v.num_shared
                        emb_pad[v.client_id, :n] = np.asarray(
                            c.params["entity"]
                        )[v.shared_local]
                        hist_pad[v.client_id, :n] = np.asarray(
                            histories[v.client_id]
                        )
                    sc = change_scores(
                        jnp.asarray(emb_pad).reshape(-1, cfg.dim),
                        jnp.asarray(hist_pad).reshape(-1, cfg.dim),
                    ).reshape(len(clients), tel_ns_max)
                    sc = jnp.where(tel_valid, sc, -jnp.inf)
                    tel_hist = np.asarray(score_histogram(sc, tel_valid))
                    tel_overlap = np.zeros(len(clients), np.int32)
                    tel_pre = emb_pad  # post-train, pre-comm — same rows the
                    # device round's update-norm probe measures against
                uploads = []
                for c, v in zip(clients, views):
                    cid = v.client_id
                    fresh = None
                    if fpart[cid]:
                        # wire codec (and its host-side error-feedback bank,
                        # when ef=1) applied inside the coded upload; a
                        # dropped message still refreshed history and
                        # residuals — the sender cannot know it was lost
                        up, hist, res = sparse_upload_coded(
                            c.params["entity"], histories[cid], v,
                            cfg.sparsity_p, codec,
                            residuals[cid] if residuals is not None
                            else None,
                        )
                        histories[cid] = hist
                        if residuals is not None:
                            residuals[cid] = res
                        if sink is not None:
                            # realized Top-K overlap with the previous SENT
                            # upload; absent clients keep their carry
                            cur = {int(e) for e in up.entity_ids}
                            tel_overlap[cid] = len(cur & tel_prev[cid])
                            tel_prev[cid] = cur
                        k_round = sparsity_k(v.num_shared, cfg.sparsity_p)
                        codec.log_upload(
                            ledger, k_round, cfg.dim, v.num_shared
                        )
                        if fup[cid]:
                            fresh = up
                    if straggler_q is not None and cid in straggler_q:
                        # delayed delivery: this round the server sees the
                        # message sent ``lag`` sparse rounds ago; the fresh
                        # (delivery-masked) message joins the queue tail
                        delivered = straggler_q[cid].popleft()
                        straggler_q[cid].append(
                            fresh if fresh is not None
                            else _empty_upload(cid, cfg.dim)
                        )
                    else:
                        delivered = (
                            fresh if fresh is not None
                            else _empty_upload(cid, cfg.dim)
                        )
                    # dense list: personalized_aggregate indexes uploads by
                    # client id; an undelivered message is a zero-entity
                    # Upload, which contributes to no aggregate
                    uploads.append(delivered)
                downloads = personalized_aggregate(
                    uploads,
                    [v.shared_global for v in views],
                    cfg.sparsity_p,
                    rng,
                )
                for c, v, d in zip(clients, views, downloads):
                    if not fpart[v.client_id]:
                        continue  # server neither selects nor bills
                    if codec.transforms_values and len(d.entity_ids):
                        d = dataclasses.replace(
                            d,
                            agg_values=np.asarray(
                                codec.roundtrip(jnp.asarray(d.agg_values)),
                                np.float32,
                            ),
                        )
                    codec.log_download(
                        ledger, len(d.entity_ids), cfg.dim, v.num_shared
                    )
                    if fdn[v.client_id]:
                        c.params["entity"] = apply_sparse_download(
                            c.params["entity"], v, d.entity_ids,
                            d.agg_values, d.priority,
                        )
            ledger.end_round()
            if sink is not None:
                rec_host = None
                if comm:
                    tel_ages = np.where(fpart, 0, tel_ages + 1).astype(
                        np.int32
                    )
                    if residuals is not None:
                        res_pad = np.zeros(
                            (len(clients), tel_ns_max, cfg.dim), np.float32
                        )
                        for v in views:
                            res_pad[v.client_id, : v.num_shared] = residuals[
                                v.client_id
                            ]
                        res_mass_h = np.asarray(
                            residual_mass(jnp.asarray(res_pad))
                        )
                    else:
                        res_mass_h = np.zeros(len(clients), np.float32)
                    if sync:
                        billed = np.where(fpart, tel_nsv, 0).astype(np.int32)
                        up_rows = dn_rows = billed
                        overlap = np.zeros(len(clients), np.int32)
                        hist_rec = np.zeros(
                            (len(clients), NUM_SCORE_BUCKETS), np.int32
                        )
                    else:
                        up_rows = np.where(
                            fpart,
                            [
                                sparsity_k(v.num_shared, cfg.sparsity_p)
                                for v in views
                            ],
                            0,
                        ).astype(np.int32)
                        dn_rows = np.array(
                            [
                                len(d.entity_ids) if fpart[v.client_id] else 0
                                for v, d in zip(views, downloads)
                            ],
                            np.int32,
                        )
                        overlap = tel_overlap
                        hist_rec = tel_hist
                    # health-probe twins: post-round padded rows through the
                    # SAME jit helpers the device records use, so wherever
                    # the trajectory matches bitwise, the probes do too
                    post_pad = jnp.asarray(_tel_rows_pad())
                    div_mean_h, div_max_h = shared_divergence(
                        post_pad, tel_gid, tel_valid, num_global_entities
                    )
                    rec_host = RoundTelemetry(
                        up_rows=up_rows, dn_rows=dn_rows, overlap=overlap,
                        res_mass=res_mass_h,
                        part=fpart.astype(np.float32),
                        up_ok=fup.astype(np.float32),
                        dn_ok=fdn.astype(np.float32),
                        age=tel_ages, score_hist=hist_rec,
                        div_mean=np.asarray(div_mean_h),
                        div_max=np.asarray(div_max_h),
                        upd_norm=np.asarray(update_norm(
                            post_pad, jnp.asarray(tel_pre), tel_valid
                        )),
                        nonfinite=np.asarray(
                            nonfinite_count(post_pad, tel_valid)
                        ),
                    )
                _emit_round_event(
                    sink, codec, cfg.dim, views, kind, t, rec_host
                )

        # ------------------------------------------------------- evaluation
        # terminal-eval guarantee: when rounds is not a multiple of the eval
        # cadence, the final partial span still ends with an eval boundary
        # (otherwise the last rounds are never evaluated and can never win
        # the best-model snapshot)
        at_boundary = (t + 1) % ee == 0 or (t + 1) == cfg.rounds
        if at_boundary and eval_boundary(t + 1):
            break

    return _finish(
        cfg, clients, use_device, cycle if use_device else None,
        state if use_device else None, pending if use_device else None,
        views, codec, ledger, eval_history, best, rounds_run,
        evaluator, sched=sched if faulted else None, sink=sink,
    )


def _finish(
    cfg, clients, use_device, cycle, state, pending,
    views, codec, ledger, eval_history, best, rounds_run, evaluator=None,
    sched=None, sink=None,
) -> FederatedResult:
    """Final flush + best-snapshot restore + test evaluation.

    Device engines restore the best on-device snapshot into the federation
    state, run the device-batched test eval, and only then materialize the
    tables into the per-client params (the single terminal host transfer).
    With telemetry on this also emits the terminal ``eval`` (test split) and
    ``ledger`` (real-vs-shadow reconciliation) events.
    """
    if use_device:
        _flush_ledger(
            ledger, pending, views, codec, cfg.dim, cycle.k_per_client,
            sched=sched, sink=sink,
        )
        if best["snap"] is not None:
            state = FederationState(
                state.arrays._replace(params=best["snap"]), state.key
            )
        test = aggregate_eval_block(
            evaluator.evaluate(state.arrays.params, "test")
        )
        cycle.sync_clients(state, clients)
    else:
        if best["snap"] is not None:
            _restore(clients, best["snap"])
        test = weighted_average(
            [c.evaluate("test", cfg.max_eval_triples) for c in clients]
        )
    if sink is not None:
        sink.emit({
            "ev": "eval", "round": int(rounds_run), "split": "test",
            "mrr": float(test["mrr"]), "hits10": float(test["hits10"]),
            "params_transmitted": ledger.params_transmitted,
            "bytes": ledger.bytes_int8_signs,
        })
        _emit_ledger_event(sink, ledger)
    return FederatedResult(
        config=cfg,
        eval_history=eval_history,
        ledger=ledger,
        best_round=int(best["round"]),
        val_mrr_cg=float(best["mrr"]),
        test_mrr_cg=float(test["mrr"]),
        test_hits10_cg=float(test["hits10"]),
        rounds_run=rounds_run,
    )


def _run_federated_tiered(
    clients_data: list[ClientData],
    num_global_entities: int,
    cfg: FederatedConfig,
    verbose: bool = False,
    sink=None,
) -> FederatedResult:
    """The host-tiered simulation loop (engine="tiered" / host_store=True).

    Same round schedule, ledger accounting, eval cadence, patience, and
    best-snapshot protocol as the dense device engines, but federation
    state lives in :class:`repro.core.store.HostTieredStore`: the device
    holds the pinned shared prefix + a bounded row cache, and each eval
    boundary materializes the full tables once (the tiered tradeoff — the
    dense engines never move entity tables across the host).

    The tiered engine trains clients in lockstep, so train sets are
    truncated to the common minimum triple count up front.
    """
    n_tr = min(len(d.train) for d in clients_data)
    if verbose and any(len(d.train) != n_tr for d in clients_data):
        print(f"tiered engine: truncating train sets to lockstep ({n_tr} "
              f"triples/client)")
    train_data = [
        dataclasses.replace(d, train=d.train[:n_tr]) for d in clients_data
    ]

    def mk_clients():
        return [
            KGEClient(
                d, method=cfg.method, dim=cfg.dim, gamma=cfg.gamma,
                batch_size=cfg.batch_size, num_negatives=cfg.num_negatives,
                lr=cfg.lr,
                adversarial_temperature=cfg.adversarial_temperature,
                seed=cfg.seed,
            )
            for d in train_data
        ]

    clients = mk_clients()
    views = build_comm_views(
        [d.local_to_global for d in clients_data], num_global_entities
    )
    codec_spec = "int8" if cfg.quantize_upload else cfg.codec
    codec = parse_codec_spec(codec_spec)
    eng = TieredCycleEngine(
        clients, views, num_global_entities,
        sparsity_p=cfg.sparsity_p, local_epochs=cfg.local_epochs,
        codec=codec, cache_slots=cfg.cache_slots,
        stage_steps=cfg.stage_steps, telemetry=sink is not None,
    )
    store, ts = eng.init_state(mk_clients(), seed=cfg.seed + 777)
    evaluator = BatchedEvaluator(
        clients_data, method=cfg.method, gamma=cfg.gamma, e_max=eng.e_max,
        max_triples=cfg.max_eval_triples, splits=("valid", "test"),
        known=[c._known for c in clients],
    )
    ledger = CommLedger()
    pending: list = []
    # per-pending-round cache hit/miss/eviction deltas for the round events
    cache_stats: list = [] if sink is not None else None
    tel_prev_stats = (
        {k: store.stats[k] for k in ("hits", "misses", "evictions")}
        if sink is not None else None
    )
    eval_history: list[tuple[int, float, float]] = []
    best = {"mrr": -1.0, "round": 0, "snap": None, "hits": 0.0}
    declines = 0
    prev_mrr = -1.0
    rounds_run = 0
    ee = max(cfg.eval_every, 10) if cfg.protocol == "single" else cfg.eval_every

    for t in range(cfg.rounds):
        rounds_run = t + 1
        kind = round_kind(t, cfg.protocol, cfg.sync_interval)
        rec = None
        if sink is not None:
            ts, down, _loss, rec = eng.run_cycle(store, ts, kind)
            snap_stats = {
                k: store.stats[k] for k in ("hits", "misses", "evictions")
            }
            cache_stats.append(
                {k: snap_stats[k] - tel_prev_stats[k] for k in snap_stats}
            )
            tel_prev_stats = snap_stats
        else:
            ts, down, _loss = eng.run_cycle(store, ts, kind)
        pending.append((kind, down if kind == "sparse" else None, t, rec))
        if (t + 1) % ee == 0 or (t + 1) == cfg.rounds:
            _flush_ledger(
                ledger, pending, views, codec, cfg.dim, eng.k_per_client,
                sink=sink, cache_stats=cache_stats,
            )
            params = eng.materialize_params(store, ts)
            val = aggregate_eval_block(evaluator.evaluate(params, "valid"))
            if sink is not None:
                sink.emit({
                    "ev": "eval", "round": t + 1, "split": "valid",
                    "mrr": float(val["mrr"]),
                    "hits10": float(val["hits10"]),
                    "params_transmitted": ledger.params_transmitted,
                    "bytes": ledger.bytes_int8_signs,
                })
            eval_history.append((t + 1, val["mrr"], val["hits10"]))
            if verbose:
                print(
                    f"round {t + 1:4d}  val MRR {val['mrr']:.4f}  "
                    f"Hits@10 {val['hits10']:.4f}  "
                    f"params {ledger.params_transmitted:.3e}  "
                    f"cache hit {store.hit_rate:.3f}"
                )
            if val["mrr"] > best["mrr"]:
                best = {
                    "mrr": val["mrr"], "round": t + 1, "hits": val["hits10"],
                    "snap": {k: np.asarray(v) for k, v in params.items()},
                }
            declines = declines + 1 if val["mrr"] < prev_mrr else 0
            prev_mrr = val["mrr"]
            if sink is not None and sink.monitor is not None \
                    and sink.monitor.should_stop():
                # graceful fail-fast (mirrors eval_boundary): the terminal
                # flush + ledger event below still run
                if verbose:
                    print(f"round {t + 1:4d}  stopping on fail-level alert")
                break
            if declines >= cfg.patience:
                break

    _flush_ledger(
        ledger, pending, views, codec, cfg.dim, eng.k_per_client,
        sink=sink, cache_stats=cache_stats,
    )
    if best["snap"] is not None:
        params = {k: jnp.asarray(v) for k, v in best["snap"].items()}
    else:
        params = eng.materialize_params(store, ts)
    test = aggregate_eval_block(evaluator.evaluate(params, "test"))
    if sink is not None:
        sink.emit({
            "ev": "eval", "round": int(rounds_run), "split": "test",
            "mrr": float(test["mrr"]), "hits10": float(test["hits10"]),
            "params_transmitted": ledger.params_transmitted,
            "bytes": ledger.bytes_int8_signs,
        })
        _emit_ledger_event(sink, ledger)
    return FederatedResult(
        config=cfg,
        eval_history=eval_history,
        ledger=ledger,
        best_round=int(best["round"]),
        val_mrr_cg=float(best["mrr"]),
        test_mrr_cg=float(test["mrr"]),
        test_hits10_cg=float(test["hits10"]),
        rounds_run=rounds_run,
    )
