"""Federation flight recorder: on-device round records + host trace spans.

Two halves, matching the two places observability costs something:

* **On-device records** — :class:`RoundTelemetry`, a pytree of per-round,
  per-client accumulators (upload/download row counts, realized Top-K
  overlap with the previous round, EF-residual L2 mass, fault masks and
  staleness ages, change-score histogram buckets).  The engines compute one
  record per comm round *inside* the compiled program — threaded through
  the same scan carries as the download counts — and the host drains them
  at eval boundaries alongside the deferred ledger flush, so recording
  costs no extra dispatches.  The carried state is :class:`TelemetryArrays`
  (the previous round's upload selection, for the overlap signal); with
  telemetry off the carry is ``None`` — zero pytree leaves, so the engines
  compile exactly the pre-telemetry programs (the PR-7 trivial-schedule
  pattern).
* **Host spans + sink** — :class:`TelemetrySink` writes newline-delimited
  JSON events (``run`` / ``round`` / ``eval`` / ``span`` / ``ledger``) to
  the path given by ``FederatedConfig.telemetry`` / ``--telemetry``;
  :func:`span` marks host-side stages (rounds, ledger flushes, eval
  calls, tiered staging, checkpoint writes).  Every span opens a
  ``jax.profiler.TraceAnnotation`` named ``telemetry/<name>`` that carries
  the span's attributes (its work counters among them), so a profiler
  trace shows the program's own host spans on the device's clock; the
  annotation costs next to nothing while no profiler runs.  A ``span``
  JSONL event is written only when a sink is installed.
* **Named scopes** — :data:`SCOPES` names the layers of the compiled
  programs (the training step, the FedS round, the eval scan); the cores
  in ``core/state.py``, ``core/engine.py`` and ``core/evaluation.py`` wrap
  their work in :func:`scope`, so each device op of a trace carries its
  layer in its HLO ``op_name``.

``tools/trace_report.py`` renders the JSONL into a per-round table and a
bytes/MRR/participation summary, and checks the **reconciliation
invariant**: replaying each round event's recorded quantities through a
shadow :class:`~repro.federated.comm.CommLedger` (same codec, same call
order) must reproduce the real ledger's totals bitwise.
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

# Change scores are 1 - cos similarity, in [0, 2]; the histogram buckets
# them uniformly over that range with the last bucket open above.
NUM_SCORE_BUCKETS = 8
SCORE_BUCKET_RANGE = 2.0


class TelemetryArrays(NamedTuple):
    """Carried telemetry state: the last upload each client actually sent.

    ``prev_idx`` (C, k_max) int32 slot indices and ``prev_msk`` (C, k_max)
    0/1 float sent-mask from the most recent sparse round in which the
    client participated; the next sparse round's realized Top-K overlap is
    measured against it.  Sync rounds pass it through unchanged (their
    exchange is dense, so "overlap" is meaningless there and recorded as 0).
    """

    prev_idx: jnp.ndarray
    prev_msk: jnp.ndarray


class RoundTelemetry(NamedTuple):
    """One comm round's per-client record, computed on device.

    All leaves lead with the client axis: ``up_rows``/``dn_rows`` (C,) int32
    rows billed on each leg, ``overlap`` (C,) int32 rows shared with the
    client's previous upload, ``res_mass`` (C,) f32 L2 norm of the
    post-round EF residual bank, ``part``/``up_ok``/``dn_ok`` (C,) 0/1 fault
    masks, ``age`` (C,) int32 rounds since last participation (post-update),
    ``score_hist`` (C, NUM_SCORE_BUCKETS) int32 change-score histogram.

    Model-health probes ride the same record: ``div_mean``/``div_max``
    (C,) f32 mean/max L2 distance of the client's post-round shared rows
    from the existence-masked cross-client mean (the inconsistency the
    paper's intermittent synchronization bounds — it collapses at sync
    rounds), ``upd_norm`` (C,) f32 L2 norm of the round's shared-row
    update, ``nonfinite`` (C,) int32 count of non-finite components in
    the client's post-round shared rows.
    """

    up_rows: jnp.ndarray
    dn_rows: jnp.ndarray
    overlap: jnp.ndarray
    res_mass: jnp.ndarray
    part: jnp.ndarray
    up_ok: jnp.ndarray
    dn_ok: jnp.ndarray
    age: jnp.ndarray
    score_hist: jnp.ndarray
    div_mean: jnp.ndarray
    div_max: jnp.ndarray
    upd_norm: jnp.ndarray
    nonfinite: jnp.ndarray


# The exact key set of a ``{"ev": "round"}`` JSONL event.  Kept as a literal
# tuple so tools/docs_lint.py can parse it without importing jax and check
# the docs/architecture.md schema table stays in sync.
ROUND_EVENT_FIELDS = (
    "round", "kind", "up_rows", "dn_rows", "overlap", "res_mass",
    "part", "up_ok", "dn_ok", "age", "score_hist",
    "div_mean", "div_max", "upd_norm", "nonfinite",
    "up_bytes", "dn_bytes", "cache_hits", "cache_misses",
    "cache_evictions", "cum_params", "cum_bytes",
)

# The named scopes of the compiled programs, outermost first within each
# layer.  A literal tuple, like ROUND_EVENT_FIELDS, so tools/docs_lint.py
# can parse it without importing jax.  Leaf scopes nest inside their
# parent (``feds/train``, ``feds/comm``, ``feds/eval``), which covers the
# work between them; names are full paths, so a leaf's name stays whole
# in an op's ``op_name`` however JAX wraps it (``transpose(jvp(...))``).
SCOPES = (
    "feds/train", "feds/train/sample", "feds/train/gather",
    "feds/train/score_grad", "feds/train/scatter", "feds/train/adam",
    "feds/comm", "feds/comm/upload", "feds/comm/aggregate",
    "feds/comm/download", "feds/comm/sync",
    "feds/eval", "feds/eval/filter", "feds/eval/score", "feds/eval/rank",
)


def init_telemetry_arrays(num_clients: int, k_max: int) -> TelemetryArrays:
    """Zeroed carry: round 0 has no previous upload, so overlap starts 0."""
    return TelemetryArrays(
        prev_idx=jnp.zeros((num_clients, k_max), jnp.int32),
        prev_msk=jnp.zeros((num_clients, k_max), jnp.float32),
    )


def telemetry_spec(p):
    """PartitionSpec pytree for TelemetryArrays (client-axis-only leaves)."""
    return TelemetryArrays(prev_idx=p, prev_msk=p)


def record_spec(p):
    """PartitionSpec pytree for RoundTelemetry (client-axis-only leaves)."""
    return RoundTelemetry(*([p] * len(RoundTelemetry._fields)))


# --------------------------------------------------------- jit-safe helpers
def score_histogram(scores, valid, entity_axis: Optional[str] = None):
    """(C, NUM_SCORE_BUCKETS) int32 histogram of change scores over valid
    rows.  ``scores`` may carry -inf on invalid slots (the engines mask
    before Top-K); the int cast clips those into bucket 0 where the zero
    ``valid`` weight drops them.  Under entity sharding the per-block counts
    are psum-reduced so every shard holds the full (replicated) histogram.
    """
    nb = NUM_SCORE_BUCKETS
    idx = jnp.clip(
        (scores * (nb / SCORE_BUCKET_RANGE)).astype(jnp.int32), 0, nb - 1
    )
    one_hot = idx[:, :, None] == jnp.arange(nb, dtype=jnp.int32)[None, None, :]
    hist = (one_hot & valid[:, :, None]).sum(axis=1).astype(jnp.int32)
    if entity_axis is not None:
        hist = jax.lax.psum(hist, entity_axis)
    return hist


def residual_mass(res, entity_axis: Optional[str] = None):
    """(C,) f32 L2 norm of each client's EF residual bank.

    Shared by the engines and the reference path's host record builder —
    same function, same (C, Ns, D) shape, same reduction order, so records
    agree bitwise whenever the residual values do.  Zero-width banks
    (non-EF codecs) reduce to exact zeros.
    """
    sq = jnp.sum(res * res, axis=(1, 2))
    if entity_axis is not None:
        sq = jax.lax.psum(sq, entity_axis)
    return jnp.sqrt(sq)


def upload_overlap(up_idx, sent_maskf, prev_idx, prev_msk):
    """(C,) int32 count of slots in this round's sent upload that were also
    in the client's previous sent upload.  Slot indices within one upload
    are distinct, so the masked pair-match sum is exactly the intersection
    size."""
    match = (up_idx[:, :, None] == prev_idx[:, None, :]).astype(jnp.float32)
    pair = match * sent_maskf[:, :, None] * prev_msk[:, None, :]
    return pair.sum(axis=(1, 2)).astype(jnp.int32)


def shared_divergence(rows, gid, valid, num_global: int,
                      axis_name: Optional[str] = None):
    """Per-client shared-entity divergence against the cross-client mean.

    ``rows`` (C, Ns, D) padded shared-row values, ``gid`` (C, Ns) int32
    global entity ids (padding slots point at ``num_global``), ``valid``
    (C, Ns) existence mask.  For every global entity the existence-masked
    cross-client mean row is formed by segment sum (one throwaway segment
    swallows the padding), then each client's valid rows are measured
    against it: ``div_mean`` averages the per-row L2 distances, ``div_max``
    takes the worst row.  A fault-free sync round makes every copy equal
    the mean, so both collapse to exactly zero — the recovery signal the
    paper's intermittent synchronization predicts.

    Callers under entity sharding must pass full-width (all-blocks) rows so
    the segment sums reduce in unsharded order (the
    :func:`~repro.core.engine.batched_sync_round` rule); ``axis_name``
    psum-reduces across a *client* mesh only.
    """
    _, _, d = rows.shape
    validf = valid.astype(rows.dtype)
    ids = jnp.where(valid, gid, num_global).reshape(-1)
    total = jax.ops.segment_sum(
        (rows * validf[:, :, None]).reshape(-1, d), ids,
        num_segments=num_global + 1)
    cnt = jax.ops.segment_sum(validf.reshape(-1), ids,
                              num_segments=num_global + 1)
    if axis_name is not None:
        total = jax.lax.psum(total, axis_name)
        cnt = jax.lax.psum(cnt, axis_name)
    mean = total / jnp.maximum(cnt, 1.0)[:, None]
    diff = rows - mean[gid]
    dist = jnp.sqrt(jnp.sum(diff * diff, axis=-1)) * validf
    div_mean = dist.sum(axis=1) / jnp.maximum(validf.sum(axis=1), 1.0)
    return div_mean, dist.max(axis=1, initial=0.0)


def update_norm(new_rows, old_rows, valid):
    """(C,) f32 L2 norm of each client's shared-row update this round.

    Padding slots are masked; like :func:`residual_mass`, callers under
    entity sharding pass full-width buffers so the reduction order matches
    the unsharded program bitwise.
    """
    diff = (new_rows - old_rows) * valid.astype(new_rows.dtype)[:, :, None]
    return jnp.sqrt(jnp.sum(diff * diff, axis=(1, 2)))


def nonfinite_count(rows, valid):
    """(C,) int32 count of non-finite components in valid shared rows.

    Integer accumulation is order-exact, so this is safe under any
    sharding; it feeds the ``nan`` alert rule.
    """
    bad = ~jnp.isfinite(rows) & valid[:, :, None]
    return bad.sum(axis=(1, 2)).astype(jnp.int32)


# -------------------------------------------------------- host sink + spans
class TelemetrySink:
    """Newline-delimited JSON event writer with span timing.

    The file opens lazily on first emit (so constructing a sink for a run
    that crashes before round 0 leaves no empty artifact) and every event is
    flushed immediately — the JSONL must survive a kill, like the
    checkpoint.  ``shadow`` is installed by the simulation: a second
    :class:`~repro.federated.comm.CommLedger` fed only from device-recorded
    telemetry, whose totals the ``ledger`` event compares against the real
    ledger's.  ``monitor`` (a :class:`~repro.core.health.HealthMonitor`,
    installed by the simulation when ``--alerts`` is set) observes every
    ``round``/``eval`` event as it drains and may append ``alert`` events
    to the stream, right after the event that fired them.
    """

    def __init__(self, path: str):
        self.path = str(path)
        self._f = None
        self.shadow = None
        self.monitor = None

    def emit(self, event: dict) -> None:
        if self._f is None:
            self._f = open(self.path, "w")
        self._f.write(json.dumps(event) + "\n")
        self._f.flush()
        if self.monitor is not None and event.get("ev") in ("round", "eval"):
            for alert in self.monitor.observe(event):
                self.emit(alert)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        t0 = time.perf_counter()
        try:
            with _annotation(name, attrs):
                yield
        finally:
            dur = time.perf_counter() - t0
            self.emit({"ev": "span", "name": name, "dur_s": dur, **attrs})

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None


_ACTIVE: Optional[TelemetrySink] = None


def _annotation(name: str, attrs: dict):
    return jax.profiler.TraceAnnotation(f"telemetry/{name}", **attrs)


def active() -> Optional[TelemetrySink]:
    """The sink installed for the current run, or None."""
    return _ACTIVE


def install(sink: Optional[TelemetrySink]) -> None:
    global _ACTIVE
    _ACTIVE = sink


@contextlib.contextmanager
def session(sink: Optional[TelemetrySink]):
    """Install ``sink`` for the duration of a run (restores the previous)."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = sink
    try:
        yield sink
    finally:
        _ACTIVE = prev


def span(name: str, **attrs):
    """Mark a host-side stage: a ``telemetry/<name>`` profiler annotation
    carrying ``attrs``, plus a timed ``span`` event in the active sink.

    Call sites are unconditional.  With no sink installed this is the
    annotation alone: no timing, no event.  ``attrs`` are host values
    (Python ints and strings); a span never reads a device array, which
    would add a host sync to the path it marks.
    """
    if _ACTIVE is None:
        return _annotation(name, attrs)
    return _ACTIVE.span(name, **attrs)


def scope(name: str):
    """``jax.named_scope(name)`` for one of :data:`SCOPES`."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; known: {SCOPES}")
    return jax.named_scope(name)
