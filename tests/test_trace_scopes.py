"""The compiled programs name their layers, and the host spans carry counters.

Two guarantees of the flight recorder's profiler side
(``core/telemetry.py``):

* every name of :data:`~repro.core.telemetry.SCOPES` appears in the HLO
  ``op_name`` metadata of the programs that run that layer — the fused
  sparse and sync rounds on both trainers (the flat one of equal
  batches-per-epoch and the vmap one of unequal), and the eval program —
  and nearly every instruction carries a ``feds/`` scope, so a device
  trace can be split by layer;
* under a profiler, ``fused_cycle``, ``_flush_ledger`` and ``evaluate``
  write ``telemetry/round``, ``telemetry/ledger_flush`` and
  ``telemetry/eval`` host events whose attributes carry the work
  counters, and with no sink installed nothing is written to disk.
"""
import glob
import os
import re

import jax
import pytest

from repro.core import telemetry
from repro.core.codecs import IdentityCodec
from repro.core.engine import make_client_mesh
from repro.core.evaluation import BatchedEvaluator
from repro.core.protocol import build_comm_views
from repro.core.state import CycleEngine
from repro.core.telemetry import SCOPES
from repro.data import generate_kg, partition_by_relation
from repro.federated.client import KGEClient
from repro.federated.comm import CommLedger
from repro.federated.simulation import _flush_ledger

# batch 32 leaves the clients unequal batches-per-epoch (the vmap trainer);
# a batch larger than any client's split gives each one batch (flat)
TRAINERS = {"vmap": 32, "flat": 4096}
TRAIN = [s for s in SCOPES if s.startswith("feds/train")]
COMM = ["feds/comm", "feds/comm/upload", "feds/comm/aggregate",
        "feds/comm/download"]
EVAL = [s for s in SCOPES if s.startswith("feds/eval")]
# instructions the compiler adds with no source op behind them: buffer
# copies and tuple plumbing carry no metadata a scope could name
PLUMBING = ("copy", "get-tuple-element", "tuple", "bitcast")
INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = .*?\s([a-z][\w-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _federation(batch_size):
    kg = generate_kg(num_entities=120, num_relations=8, num_triples=800,
                     seed=1)
    datas = partition_by_relation(kg, 2, seed=0)
    clients = [
        KGEClient(d, method="transe", dim=8, batch_size=batch_size,
                  num_negatives=4, lr=5e-3, seed=0)
        for d in datas
    ]
    views = build_comm_views([d.local_to_global for d in datas],
                             kg.num_entities)
    engine = CycleEngine(clients, views, kg.num_entities, sparsity_p=0.5,
                         local_epochs=1)
    state = engine.init_state(clients, seed=0)
    evaluator = BatchedEvaluator(
        datas, method="transe", gamma=clients[0].gamma, e_max=engine.e_max,
        max_triples=30, splits=("valid",), known=[c._known for c in clients],
    )
    return datas, clients, views, engine, state, evaluator


@pytest.fixture(scope="module", params=sorted(TRAINERS))
def fed(request):
    out = _federation(TRAINERS[request.param])
    # the two batch sizes do build the two trainers
    assert out[3]._uniform_steps == (request.param == "flat")
    return out


def _compiled(fed):
    """Compiled text of the fused sparse and sync programs and the eval
    program."""
    _, _, _, engine, state, evaluator = fed
    _, kb, kj = engine._advance(state.key)
    args = (state.arrays, kb, kj, engine.consts)
    return {
        "sparse": engine._fused_sparse.lower(*args).compile().as_text(),
        "sync": engine._fused_sync.lower(*args).compile().as_text(),
        "eval": evaluator._eval.lower(
            state.arrays.params, evaluator.banks["valid"]
        ).compile().as_text(),
    }


def test_programs_carry_every_scope(fed):
    want = {"sparse": TRAIN + COMM, "sync": TRAIN + ["feds/comm",
                                                     "feds/comm/sync"],
            "eval": EVAL}
    found = set()
    for prog, text in _compiled(fed).items():
        names = set(OP_NAME.findall(text))
        for s in want[prog]:
            assert any(s in n for n in names), (prog, s)
            found.add(s)
    assert found == set(SCOPES)


def test_nearly_every_instruction_is_scoped(fed):
    for prog, text in _compiled(fed).items():
        total = scoped = 0
        for line in text.splitlines():
            m = INSTR.match(line)
            if m is None or m.group(1) in ("parameter", "constant"):
                continue
            name = OP_NAME.search(line)
            if name is None and m.group(1) in PLUMBING:
                continue
            total += 1
            scoped += bool(name and "feds/" in name.group(1))
        assert total > 100, prog
        assert scoped >= 0.95 * total, (prog, scoped, total)


def test_scope_refuses_an_unknown_name():
    with pytest.raises(ValueError, match="unknown scope"):
        telemetry.scope("feds/train/typo")


def test_counters_by_hand():
    datas, clients, views, engine, _, evaluator = _federation(32)
    steps = [c.loader.batches_per_epoch for c in clients]
    # one device: the segmented trainer computes the real client-steps only
    assert engine.client_steps == engine.client_steps_real == sum(steps)
    assert engine.train_segments == len(set(steps)) > 1
    # under a client mesh the masked scan pads every client to the longest
    meshed = CycleEngine(clients, views, engine.num_global, sparsity_p=0.5,
                         local_epochs=1, mesh=make_client_mesh(1))
    assert meshed.client_steps == len(datas) * max(steps)
    assert meshed.client_steps_real == sum(steps) < meshed.client_steps
    assert meshed.train_segments == 1
    bank = evaluator.banks["valid"]
    counts = [min(len(d.valid), 30) for d in datas]
    assert evaluator._eval_work["valid"] == {
        "scores": len(datas) * max(counts) * 2 * evaluator.e_pad,
        # the ref dispatch reduces no candidate score on the MXU
        "scores_mxu": 0,
        "scores_real": sum(2 * n * d.num_entities
                           for n, d in zip(counts, datas)),
    }
    assert bank.triples.shape[1] == max(counts)


# legs the kernels reduce on the MXU: TransE's and the bilinear family's
# (ProjE's head leg does not fold into a query row), none for RotatE
@pytest.mark.parametrize("method,legs", [("transe", 2), ("rotate", 0),
                                         ("complex", 2), ("proje", 1)])
def test_mxu_counter_under_the_kernels(monkeypatch, method, legs):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    kg = generate_kg(num_entities=120, num_relations=8, num_triples=800,
                     seed=1)
    datas = partition_by_relation(kg, 2, seed=0)
    evaluator = BatchedEvaluator(
        datas, method=method, gamma=8.0,
        e_max=max(d.num_entities for d in datas), max_triples=30,
        splits=("valid",),
    )
    work = evaluator._eval_work["valid"]
    assert work["scores_mxu"] == work["scores"] * legs // 2
    assert work["scores"] == (len(datas) * evaluator.banks["valid"]
                              .triples.shape[1] * 2 * evaluator.e_pad)


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("telemetry/"):
                    out.append((e.name, dict(e.stats)))
    return out


def test_spans_reach_the_profiler_with_their_counters(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    assert telemetry.active() is None
    _, _, views, engine, state, evaluator = _federation(32)
    # compile outside the trace
    state, _, _ = engine.fused_cycle(state, sync=False, t=0)
    evaluator.evaluate(state.arrays.params, "valid")
    pending = []
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        for t, sync in ((1, False), (2, True)):
            state, down, _ = engine.fused_cycle(state, sync=sync, t=t)
            pending.append(("sync" if sync else "sparse", down, t, None))
        _flush_ledger(CommLedger(), pending, views, IdentityCodec(), 8,
                      engine.k_per_client)
        evaluator.evaluate(state.arrays.params, "valid")
    finally:
        jax.profiler.stop_trace()
    events = _host_events(trace_dir)
    rounds = [a for n, a in events if n == "telemetry/round"]
    assert [(a["t"], a["kind"]) for a in rounds] == [(1, "sparse"),
                                                     (2, "sync")]
    for a in rounds:
        assert a["client_steps"] == engine.client_steps
        assert a["client_steps_real"] == engine.client_steps_real
        assert a["train_segments"] == engine.train_segments
    flushes = [a for n, a in events if n == "telemetry/ledger_flush"]
    assert flushes == [{"rounds": 2}]
    evals = [a for n, a in events if n == "telemetry/eval"]
    assert evals == [dict(split="valid", **evaluator._eval_work["valid"])]
    # no sink installed: the spans wrote nothing beside the trace
    assert not [p for p in glob.glob(str(tmp_path / "**"), recursive=True)
                if p.endswith(".jsonl")]


def test_a_sink_still_gets_its_span_events(tmp_path):
    sink = telemetry.TelemetrySink(str(tmp_path / "t.jsonl"))
    with telemetry.session(sink):
        with telemetry.span("round", t=4, client_steps=9):
            pass
    sink.close()
    text = (tmp_path / "t.jsonl").read_text()
    assert '"name": "round"' in text and '"client_steps": 9' in text
    assert telemetry.active() is None
    assert len(text.splitlines()) == 1
