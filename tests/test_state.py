"""Fused cycle (repro.core.state) == per-round batched path, exactly.

The fused program and the per-round ``engine="batched"`` oracle share the
same device-resident :class:`FederationState`, the same threaded PRNG key
schedule (one 3-way split per cycle), and the same ``train_core`` /
``comm_core`` functions — the ONLY difference is whether train and
communicate compile as one program or two.  So with the same seeds they must
produce the same eval trajectory and bitwise-identical ledgers, over
randomized heterogeneous federations (different per-client entity counts,
triple counts, batches-per-epoch, and clients smaller than the batch size).

The same contract extends one level up to ``engine="superstep"``
(:class:`SuperstepEngine`): a whole ISM span scanned into ONE program must be
trajectory- and ledger-bitwise-identical to the same rounds driven one
``fused_cycle`` call at a time.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import make_client_mesh
from repro.core.protocol import build_comm_views
from repro.core.state import CycleEngine, SuperstepEngine, step_segments
from repro.core.sync import compress_schedule
from repro.data import generate_kg, partition_by_relation
from repro.federated.client import KGEClient
from repro.federated.simulation import FederatedConfig, run_federated
from repro.kge.scoring import registered_methods


def _instance(seed):
    """Randomized heterogeneous federation + config (seeded, not hypothesis:
    the container has no hypothesis wheel and determinism helps bisection)."""
    rng = np.random.default_rng(seed)
    num_clients = int(rng.integers(2, 4))
    kg = generate_kg(
        num_entities=int(rng.integers(100, 180)),
        num_relations=3 * num_clients,
        num_triples=int(rng.integers(700, 1400)),
        seed=int(rng.integers(0, 1000)),
    )
    clients = partition_by_relation(kg, num_clients, seed=int(rng.integers(0, 10)))
    cfg = dict(
        method="transe",
        dim=int(rng.choice([8, 16])),
        rounds=5,
        local_epochs=int(rng.integers(1, 3)),
        # deliberately larger than some clients' train split sometimes, to
        # exercise padded batch rows (B_c = min(batch, T_c))
        batch_size=int(rng.choice([32, 64, 512])),
        num_negatives=8,
        lr=5e-3,
        sparsity_p=float(rng.choice([0.3, 0.5, 1.0])),
        sync_interval=2,
        eval_every=2,
        patience=99,
        max_eval_triples=40,
        seed=int(rng.integers(0, 100)),
    )
    return kg, clients, cfg


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("protocol", ["feds"])
def test_fused_matches_batched_trajectory_and_ledger(seed, protocol):
    kg, clients, cfg = _instance(seed)
    fused = run_federated(
        clients, kg.num_entities,
        FederatedConfig(protocol=protocol, engine="fused", **cfg),
    )
    batched = run_federated(
        clients, kg.num_entities,
        FederatedConfig(protocol=protocol, engine="batched", **cfg),
    )
    assert fused.eval_history == batched.eval_history
    assert fused.ledger.history == batched.ledger.history
    assert fused.ledger.params_transmitted == batched.ledger.params_transmitted
    assert fused.ledger.bytes_int8_signs == batched.ledger.bytes_int8_signs
    assert fused.test_mrr_cg == batched.test_mrr_cg
    assert np.isfinite(fused.test_mrr_cg)


def _small_federation(seed=0):
    kg = generate_kg(num_entities=80, num_relations=6, num_triples=400,
                     seed=seed)
    return kg, partition_by_relation(kg, 2, seed=seed)


@pytest.mark.parametrize("method", sorted(registered_methods()))
def test_all_engines_agree_for_every_registered_method(method):
    """Engine-equivalence sweep over the WHOLE scoring registry: for every
    registered method the three device engines (fused, batched, superstep)
    are trajectory- and ledger-bitwise-identical, and the ragged numpy
    reference protocol transmits the bitwise-same ledger (its training
    arithmetic is an independent host oracle with a different summation
    order, so trajectories agree only statistically — finiteness pinned).
    Catches any engine still dispatching on a hardcoded method list instead
    of the registry."""
    kg, clients = _small_federation()
    cfg = dict(method=method, dim=8, rounds=3, local_epochs=1, batch_size=32,
               num_negatives=4, lr=5e-3, sync_interval=2, eval_every=2,
               patience=99, max_eval_triples=20, seed=3)
    runs = {
        eng: run_federated(clients, kg.num_entities,
                           FederatedConfig(engine=eng, **cfg))
        for eng in ("fused", "batched", "superstep", "reference")
    }
    fused = runs["fused"]
    assert np.isfinite(fused.test_mrr_cg)
    for eng in ("batched", "superstep"):
        assert fused.eval_history == runs[eng].eval_history, eng
        assert fused.ledger.history == runs[eng].ledger.history, eng
        assert fused.test_mrr_cg == runs[eng].test_mrr_cg, eng
    for eng in ("batched", "superstep", "reference"):
        assert fused.ledger.params_transmitted == \
            runs[eng].ledger.params_transmitted, eng
        assert fused.ledger.bytes_int8_signs == \
            runs[eng].ledger.bytes_int8_signs, eng
    assert np.isfinite(runs["reference"].test_mrr_cg)


@pytest.mark.parametrize("method", ["protate", "distmult"])
def test_engines_agree_through_ef_codec_sweep(method):
    """Same device-engine parity through an error-feedback wire codec
    (int8:ef=1) for one method of each family — EF residual banks ride the
    engine state, so this catches any registry-routed method whose state
    layout breaks the banked-residual threading."""
    kg, clients = _small_federation(1)
    cfg = dict(method=method, dim=8, rounds=4, local_epochs=1, batch_size=32,
               num_negatives=4, lr=5e-3, sync_interval=2, eval_every=2,
               patience=99, max_eval_triples=20, seed=5, codec="int8:ef=1")
    runs = [
        run_federated(clients, kg.num_entities,
                      FederatedConfig(engine=eng, **cfg))
        for eng in ("fused", "batched", "superstep")
    ]
    for other in runs[1:]:
        assert runs[0].eval_history == other.eval_history
        assert runs[0].ledger.history == other.ledger.history
        assert runs[0].test_mrr_cg == other.test_mrr_cg
    assert np.isfinite(runs[0].test_mrr_cg)


def test_fused_matches_batched_quantized_fedep():
    """Same parity through the int8 wire codec and the sync-every-round
    protocol (exercises the sync-round leg of the fused program)."""
    kg, clients, cfg = _instance(7)
    for protocol, quant in (("fedep", False), ("feds", True)):
        fused = run_federated(
            clients, kg.num_entities,
            FederatedConfig(protocol=protocol, engine="fused",
                            quantize_upload=quant, **cfg),
        )
        batched = run_federated(
            clients, kg.num_entities,
            FederatedConfig(protocol=protocol, engine="batched",
                            quantize_upload=quant, **cfg),
        )
        assert fused.eval_history == batched.eval_history, protocol
        assert fused.ledger.history == batched.ledger.history, protocol


def test_ledger_totals_independent_of_eval_cadence():
    """Deferred device-side accounting: flushing pending rounds at different
    eval boundaries must produce a bitwise-identical ledger (evaluation
    never feeds back into training except through early stopping, which the
    large patience disables)."""
    kg, clients, cfg = _instance(3)
    cfg = dict(cfg, rounds=6, patience=99)
    ledgers = []
    for eval_every in (1, 3, 6):
        res = run_federated(
            clients, kg.num_entities,
            FederatedConfig(protocol="feds", engine="fused",
                            **dict(cfg, eval_every=eval_every)),
        )
        ledgers.append(res.ledger)
    assert ledgers[0].history == ledgers[1].history == ledgers[2].history
    assert (
        ledgers[0].bytes_int8_signs
        == ledgers[1].bytes_int8_signs
        == ledgers[2].bytes_int8_signs
    )


# ------------------------------------------------------ superstep == fused
def test_compress_schedule_rle():
    assert compress_schedule(["sparse", "sparse", "sync"]) == (
        ("sparse", 2), ("sync", 1),
    )
    assert compress_schedule(["sync", "sparse", "sync", "sync"]) == (
        ("sync", 1), ("sparse", 1), ("sync", 2),
    )
    assert compress_schedule([]) == ()
    with pytest.raises(ValueError, match="unknown round kind"):
        compress_schedule(["sparse", "dense"])


@pytest.mark.parametrize("seed", [0, 2])
def test_superstep_matches_fused_trajectory_and_ledger(seed):
    """engine="superstep" (one scanned program per eval span) must be
    trajectory- and ledger-bitwise-identical to engine="fused" (one program
    per round) over the same ISM schedule."""
    kg, clients, cfg = _instance(seed)
    fused = run_federated(
        clients, kg.num_entities,
        FederatedConfig(protocol="feds", engine="fused", **cfg),
    )
    sstep = run_federated(
        clients, kg.num_entities,
        FederatedConfig(protocol="feds", engine="superstep", **cfg),
    )
    assert fused.eval_history == sstep.eval_history
    assert fused.ledger.history == sstep.ledger.history
    assert fused.ledger.params_transmitted == sstep.ledger.params_transmitted
    assert fused.ledger.bytes_int8_signs == sstep.ledger.bytes_int8_signs
    assert fused.test_mrr_cg == sstep.test_mrr_cg
    assert fused.rounds_run == sstep.rounds_run
    assert np.isfinite(sstep.test_mrr_cg)


def test_superstep_equals_sequential_fused_cycles():
    """One superstep over an ISM period (s sparse + 1 sync) + a train-only
    round must leave bitwise-identical device state to the same rounds driven
    one fused_cycle/train_cycle call at a time."""
    kg = generate_kg(num_entities=130, num_relations=9, num_triples=1000, seed=0)
    cd = partition_by_relation(kg, 3, seed=0)

    def mk():
        return [
            KGEClient(d, method="transe", dim=8, batch_size=48, num_negatives=4,
                      lr=5e-3, seed=0)
            for d in cd
        ]

    views = build_comm_views([d.local_to_global for d in cd], kg.num_entities)
    engine = SuperstepEngine(mk(), views, kg.num_entities,
                             sparsity_p=0.5, local_epochs=2)
    kinds = ("sparse", "sparse", "sync", "none")

    sa = engine.init_state(mk(), seed=3)
    sa, per_round, _losses = engine.superstep(sa, kinds)
    downs_a = [np.asarray(d) for k, d in per_round if k == "sparse"]
    assert [k for k, _ in per_round] == list(kinds)
    assert all(d is None for k, d in per_round if k != "sparse")

    sb = engine.init_state(mk(), seed=3)
    downs_b = []
    for kind in kinds:
        if kind == "none":
            sb, _jitter, _loss = engine.train_cycle(sb)
        else:
            sb, down, _loss = engine.fused_cycle(sb, sync=kind == "sync")
            if kind == "sparse":
                downs_b.append(np.asarray(down))

    np.testing.assert_array_equal(np.asarray(sa.key), np.asarray(sb.key))
    np.testing.assert_array_equal(np.asarray(downs_a), np.asarray(downs_b))
    for name, a, b in (
        ("entity", sa.arrays.params["entity"], sb.arrays.params["entity"]),
        ("relation", sa.arrays.params["relation"], sb.arrays.params["relation"]),
        ("hist", sa.arrays.hist, sb.arrays.hist),
        ("mu_e", sa.arrays.opt.mu["entity"], sb.arrays.opt.mu["entity"]),
        ("nu_e", sa.arrays.opt.nu["entity"], sb.arrays.opt.nu["entity"]),
        ("step", sa.arrays.opt.step, sb.arrays.opt.step),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


# ----------------------------------------------------------- state invariants
def _make_engine(num_clients=3, seed=0, **kw):
    kg = generate_kg(num_entities=130, num_relations=3 * num_clients,
                     num_triples=1000, seed=seed)
    cd = partition_by_relation(kg, num_clients, seed=0)
    clients = [
        KGEClient(d, method="transe", dim=8, batch_size=48, num_negatives=4,
                  lr=5e-3, seed=seed)
        for d in cd
    ]
    views = build_comm_views([d.local_to_global for d in cd], kg.num_entities)
    engine = CycleEngine(clients, views, kg.num_entities,
                         sparsity_p=0.5, local_epochs=2, **kw)
    return engine, clients


def test_fused_cycle_padding_rows_stay_zero():
    """Padded entity rows / shared slots must never be touched by training
    (never sampled), the optimizer (zero moments), or the round (masked)."""
    engine, clients = _make_engine()
    state = engine.init_state(clients, seed=11)
    for sync in (False, True, False):
        state, _down, _loss = engine.fused_cycle(state, sync=sync)
    ent = np.asarray(state.arrays.params["entity"])
    mu = np.asarray(state.arrays.opt.mu["entity"])
    hist = np.asarray(state.arrays.hist)
    for c, cl in enumerate(clients):
        n = cl.model.num_entities
        np.testing.assert_array_equal(ent[c, n:], 0.0)
        np.testing.assert_array_equal(mu[c, n:], 0.0)
        ns = engine.views[c].num_shared
        np.testing.assert_array_equal(hist[c, ns:], 0.0)


def test_state_roundtrips_through_clients():
    """init_state -> sync_clients is the identity on per-client params."""
    engine, clients = _make_engine()
    before = [{k: np.asarray(v) for k, v in c.params.items()} for c in clients]
    state = engine.init_state(clients, seed=0)
    engine.sync_clients(state, clients)
    for b, c in zip(before, clients):
        np.testing.assert_array_equal(b["entity"], np.asarray(c.params["entity"]))
        np.testing.assert_array_equal(b["relation"], np.asarray(c.params["relation"]))


def test_training_reduces_loss():
    """The device-resident trainer actually learns (loss falls over cycles)."""
    engine, clients = _make_engine()
    state = engine.init_state(clients, seed=0)
    state, _, first = engine.train_cycle(state)
    for _ in range(8):
        state, _, last = engine.train_cycle(state)
    assert float(np.asarray(last).mean()) < float(np.asarray(first).mean())


def test_heterogeneous_hyperparams_rejected():
    engine, clients = _make_engine()
    clients[1].lr = clients[1].lr * 2
    with pytest.raises(ValueError, match="homogeneous"):
        CycleEngine(clients, engine.views, engine.num_global,
                    sparsity_p=0.5, local_epochs=2)


def test_flat_trainer_rejects_unequal_adam_steps():
    """The flat fast path shares one Adam step count; clients arriving with
    divergent counts must be rejected instead of silently mis-corrected."""
    kg = generate_kg(num_entities=130, num_relations=6, num_triples=1000, seed=0)
    cd = partition_by_relation(kg, 2, seed=0)
    clients = [
        # batch >= every split size => one batch per epoch for all clients
        KGEClient(d, method="transe", dim=8, batch_size=10_000,
                  num_negatives=4, lr=5e-3, seed=0)
        for d in cd
    ]
    views = build_comm_views([d.local_to_global for d in cd], kg.num_entities)
    engine = CycleEngine(clients, views, kg.num_entities,
                         sparsity_p=0.5, local_epochs=1)
    assert engine._uniform_steps
    engine.init_state(clients)  # equal (zero) steps: fine
    clients[0].train_local(1)  # client 0 advances its Adam step alone
    with pytest.raises(ValueError, match="lockstep"):
        engine.init_state(clients)


# ------------------------------------------ segmented trainer == masked scan
def test_step_segments_cover_every_real_step_once_in_order():
    """The static schedule: each client's real scan indices appear exactly
    once and in order, and the segments are non-empty over shrinking sets
    of clients."""
    segs = step_segments([105, 269, 109], 1)  # R3's batches at B=512
    assert [(c.tolist(), i.shape[1]) for c, i in segs] == [
        ([0, 1, 2], 105), ([1, 2], 4), ([1], 160)
    ]
    rng = np.random.default_rng(0)
    for _ in range(200):
        steps = rng.integers(1, 12, size=int(rng.integers(1, 6)))
        epochs = int(rng.integers(1, 4))
        mask = np.tile(np.arange(steps.max()), epochs)[None, :] < steps[:, None]
        seen = [[] for _ in steps]
        prev = None
        for clients, idx in step_segments(steps, epochs):
            assert len(clients) > 0 and idx.shape[1] > 0
            assert idx.shape[0] == len(clients)
            if prev is not None:
                assert set(clients.tolist()) < set(prev.tolist())
            prev = clients
            for c, row in zip(clients, idx):
                seen[c].extend(row.tolist())
        for c, got in enumerate(seen):
            assert got == np.flatnonzero(mask[c]).tolist(), (steps, epochs)


@pytest.mark.parametrize("method,local_epochs,unequal_batches,seed", [
    ("transe", 1, False, 0),
    ("transe", 3, True, 1),
    ("complex", 1, True, 2),
    ("complex", 3, False, 3),
])
def test_segmented_trainer_matches_masked_scan(method, local_epochs,
                                               unequal_batches, seed):
    """On one device the trainer scans only the clients that still have
    real steps; on a (one-device) client mesh it runs the masked scan over
    every client.  Over randomized heterogeneous federations both give the
    same trajectory: the same Adam step counts and download counts (the
    ledger's), exactly, and params, moments, history and losses to 1e-6 of
    each leaf's largest value.  Not bitwise: XLA:CPU fuses plain Adam's
    arithmetic otherwise than Adam behind the masked scan's ``select``, and
    the results differ in the last bit."""
    rng = np.random.default_rng(seed)
    num_clients = int(rng.integers(2, 5))
    kg = generate_kg(num_entities=int(rng.integers(100, 180)),
                     num_relations=3 * num_clients,
                     num_triples=int(rng.integers(700, 1400)),
                     seed=int(rng.integers(0, 1000)))
    cd = partition_by_relation(kg, num_clients, seed=seed)
    dim = int(rng.choice([8, 16]))
    batch = [32] * num_clients
    if unequal_batches:
        batch[0] = 48

    def mk():
        return [KGEClient(d, method=method, dim=dim, batch_size=b,
                          num_negatives=4, lr=5e-3, seed=seed)
                for d, b in zip(cd, batch)]

    views = build_comm_views([d.local_to_global for d in cd], kg.num_entities)
    kw = dict(sparsity_p=0.5, local_epochs=local_epochs)
    seg = CycleEngine(mk(), views, kg.num_entities, **kw)
    masked = CycleEngine(mk(), views, kg.num_entities,
                         mesh=make_client_mesh(1), **kw)
    assert seg._segments is not None and len(seg._segments) > 1
    assert masked._segments is None
    assert seg._uniform_batches != unequal_batches
    sa = seg.init_state(mk(), seed=seed)
    sb = masked.init_state(mk(), seed=seed)

    def close(a, b, what):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.abs(a - b).max() <= 1e-6 * np.abs(b).max(), what

    for r, sync in enumerate((False, True, False)):
        sa, da, la = seg.fused_cycle(sa, sync=sync)
        sb, db, lb = masked.fused_cycle(sb, sync=sync)
        np.testing.assert_array_equal(np.asarray(da), np.asarray(db))
        close(la, lb, ("loss", r))
    np.testing.assert_array_equal(np.asarray(sa.arrays.opt.step),
                                  np.asarray(sb.arrays.opt.step))
    for name in ("params", "opt", "hist"):
        for i, (a, b) in enumerate(zip(jax.tree.leaves(getattr(sa.arrays, name)),
                                       jax.tree.leaves(getattr(sb.arrays, name)))):
            close(a, b, (name, i))


# ----------------------------------------------------- eval filter-mask cache
def test_eval_filter_cache_matches_bruteforce():
    from repro.core.evaluation import unpack_filter_words

    _, clients = _make_engine()
    cl = clients[0]
    triples = cl.data.valid
    assert cl._filter_cache == {}  # lazy: nothing built at construction
    n = int(triples.shape[0])
    e = cl.data.num_entities
    cl.evaluate("valid", n)
    ft_w, fh_w = cl._filter_cache[("valid", n)]
    assert ft_w.shape == (n, (e + 31) // 32) and ft_w.dtype == np.uint32
    ft = np.asarray(unpack_filter_words(jnp.asarray(ft_w), e))
    fh = np.asarray(unpack_filter_words(jnp.asarray(fh_w), e))
    for i, (h, r, t) in enumerate(triples.tolist()):
        tails = set(cl._known.get(("t", h, r), set())) - {t}
        heads = set(cl._known.get(("h", r, t), set())) - {h}
        assert set(np.nonzero(ft[i])[0].tolist()) == tails
        assert set(np.nonzero(fh[i])[0].tolist()) == heads
    # repeated evaluations are deterministic; a smaller request gets its own
    # (split, n_rows) entry sliced from the cached superset, so the cache
    # never serves rows from a stale larger build
    m = min(50, n - 1)
    assert cl.evaluate("valid", m) == cl.evaluate("valid", m)
    np.testing.assert_array_equal(cl._filter_cache[("valid", m)][0], ft_w[:m])


# ------------------------------------------------------------- SPMD == host
_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys, json
sys.path.insert(0, "src")
import numpy as np
from repro.core.engine import make_client_mesh
from repro.core.protocol import build_comm_views
from repro.core.state import CycleEngine
from repro.data import generate_kg, partition_by_relation
from repro.federated.client import KGEClient

kg = generate_kg(num_entities=120, num_relations=8, num_triples=900, seed=1)
cd = partition_by_relation(kg, 2, seed=0)
def mk():
    return [KGEClient(d, method="transe", dim=8, batch_size=32,
                      num_negatives=4, lr=5e-3, seed=0) for d in cd]
views = build_comm_views([d.local_to_global for d in cd], kg.num_entities)

host = CycleEngine(mk(), views, kg.num_entities, sparsity_p=0.5, local_epochs=2)
pod = CycleEngine(mk(), views, kg.num_entities, sparsity_p=0.5, local_epochs=2,
                  mesh=make_client_mesh(2))
sh = host.init_state(mk(), seed=7)
sp = pod.init_state(mk(), seed=7)
out = {}
for name, sync in (("sparse", False), ("sync", True)):
    sh, dh, lh = host.fused_cycle(sh, sync=sync)
    sp, dp, lp = pod.fused_cycle(sp, sync=sync)
    out[name] = {
        "emb": float(np.abs(np.asarray(sh.arrays.params["entity"])
                            - np.asarray(sp.arrays.params["entity"])).max()),
        "hist": float(np.abs(np.asarray(sh.arrays.hist)
                             - np.asarray(sp.arrays.hist)).max()),
        "down": (np.asarray(dh) == np.asarray(dp)).all().item(),
    }
print(json.dumps(out))
"""


def test_fused_cycle_spmd_matches_host():
    """One shard_map cycle program over >= 2 CPU devices == single-device jit."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", _WORKER], capture_output=True, text=True,
        env=env, timeout=900,
        cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    for name, rec in out.items():
        assert rec["emb"] < 1e-5, (name, rec)
        assert rec["hist"] < 1e-5, (name, rec)
        assert rec["down"], (name, rec)


_POD_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys, json
sys.path.insert(0, "src")
from repro.data import generate_kg, partition_by_relation
from repro.federated.simulation import FederatedConfig, run_federated

kg = generate_kg(num_entities=120, num_relations=8, num_triples=900, seed=1)
clients = partition_by_relation(kg, 2, seed=0)
base = dict(method="transe", dim=8, rounds=4, local_epochs=1, batch_size=32,
            num_negatives=4, lr=5e-3, sparsity_p=0.5, sync_interval=2,
            eval_every=2, patience=99, max_eval_triples=30, seed=0)
host = run_federated(clients, kg.num_entities,
                     FederatedConfig(protocol="feds", engine="fused", **base))
pod = run_federated(clients, kg.num_entities,
                    FederatedConfig(protocol="feds", engine="superstep",
                                    mesh_devices=2, **base))
print(json.dumps({
    "hist_eq": host.eval_history == pod.eval_history,
    "ledger_eq": host.ledger.history == pod.ledger.history,
    "params_eq": host.ledger.params_transmitted
                 == pod.ledger.params_transmitted,
    "mrr_eq": host.test_mrr_cg == pod.test_mrr_cg,
}))
"""


def test_superstep_pod_simulation_matches_host_fused():
    """The pod-mode simulation driver (mesh_devices=2, client axis sharded
    under shard_map) must reproduce the host fused trajectory and ledger."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", _POD_WORKER], capture_output=True, text=True,
        env=env, timeout=900,
        cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out == {
        "hist_eq": True, "ledger_eq": True, "params_eq": True, "mrr_eq": True,
    }
