"""The output check catches what it is there to catch.

A run is driven through the harness at a tiny size, the look for a chip
skipped, with the timed path broken underneath; ``correct`` must come out
false.  The control (the reference in bfloat16) and the planted faults of
``control.py`` must fail the cells' limits too.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import check
import control
import harness
import kgdata
from repro.core import state as core_state
from repro.core.evaluation import BatchedEvaluator
from repro.core.state import CycleEngine, FederationState

HERE = os.path.dirname(os.path.abspath(__file__))
TRAIN, EVAL = "r3-transe.train", "r3-transe.eval"
BILINEAR_EVAL = "r5-complex.eval"


def setting(cell):
    _, cfg, traffic, limits, e2e, _ = harness.cell(os.path.dirname(HERE), cell)
    cfg = json.loads(json.dumps(cfg))
    cfg["dataset"].update(num_entities=200, num_relations=12, num_triples=3000,
                          num_clusters=4)
    cfg.update(dim=16, batch_size=64, num_negatives=4)
    return cfg, traffic, limits, e2e


def run(cell, trace=False):
    cfg, traffic, limits, e2e = setting(cell)
    return harness.execute(cfg, traffic, limits, e2e, [], seed=2**31 + 11,
                           seconds=0.5, trace=trace, t0=0.0)


def test_sound_runs_are_correct():
    assert run(TRAIN)["correct"]
    assert run(EVAL)["correct"]


def test_state_left_unchanged(monkeypatch):
    fused = CycleEngine.fused_cycle

    def frozen(self, state, sync, t=None):
        keep = jax.tree.map(jnp.copy, state.arrays)
        new, down, loss = fused(self, state, sync, t)
        return FederationState(keep, new.key), down, loss

    monkeypatch.setattr(CycleEngine, "fused_cycle", frozen)
    out = run(TRAIN)
    assert not out["correct"]
    assert out["check"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(monkeypatch):
    whole = core_state.loss_from_scores

    def half(pos, neg, method, temp=1.0, sample_weight=None):
        b = pos.shape[0] // 2
        return whole(pos[:b], neg[:b], method, temp)

    monkeypatch.setattr(core_state, "loss_from_scores", half)
    out = run(TRAIN)
    assert not out["correct"]


def test_sync_round_run_as_a_sparse_round(monkeypatch):
    fused = CycleEngine.fused_cycle

    def sparse_only(self, state, sync, t=None):
        return fused(self, state, False, t)

    monkeypatch.setattr(CycleEngine, "fused_cycle", sparse_only)
    assert not run(TRAIN)["correct"]


@pytest.mark.parametrize("cell", [TRAIN, EVAL, BILINEAR_EVAL])
def test_answer_altered_where_produced(monkeypatch, cell):
    evaluate = BatchedEvaluator.evaluate

    def altered(self, params, split):
        # one leg of one query ranked first: its reciprocal rank becomes 1
        block = evaluate(self, params, split).copy()
        block[0, 0] += (1.0 - block[0, 0]) / (2.0 * block[0, 4])
        return block

    monkeypatch.setattr(BatchedEvaluator, "evaluate", altered)
    assert not run(cell)["correct"]


def test_control_and_faults_fail_the_limits():
    cfg, traffic, limits, _ = setting(TRAIN)
    splits = kgdata.federation(cfg["dataset"], cfg["num_clients"])
    for name, numbers in control.train_readings(cfg, traffic, splits, 7).items():
        assert not check.judge(numbers, limits)[0], name
    cfg, traffic, limits, _ = setting(EVAL)
    for name, numbers in control.eval_readings(cfg, traffic, splits, 7).items():
        assert not check.judge(numbers, limits)[0], name


def test_sound_bilinear_eval_run_is_correct(monkeypatch):
    # the Pallas kernels in interpret mode, so that the run ranks through
    # bilinear_cand_score_pallas as the cell does on the chip
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert run(BILINEAR_EVAL)["correct"]


def test_bilinear_eval_control_and_fault_fail_the_limits():
    cfg, traffic, limits, _ = setting(BILINEAR_EVAL)
    splits = kgdata.federation(cfg["dataset"], cfg["num_clients"])
    for name, numbers in control.eval_readings(cfg, traffic, splits, 7).items():
        assert not check.judge(numbers, limits)[0], name


def test_federation_mrr_weighs_clients_by_their_triples():
    # MRR 0.2 over 100 triples and 0.4 over 300: the federation's is 0.35
    ref = [[0.2, 0, 0, 0, 100], [0.4, 0, 0, 0, 300]]
    got = [[0.2, 0, 0, 0, 100], [0.4 + 0.07 * 4 / 3, 0, 0, 0, 300]]
    assert check.federation_mrr_gap(got, ref) == pytest.approx(0.07 / 0.35)
    assert check.mrr_gap(got, ref) == pytest.approx(0.07 * 4 / 3 / 0.4)
