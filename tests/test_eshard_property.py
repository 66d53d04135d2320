"""Entity-axis sharding contracts.

Two layers, mirroring how the sharded engines are built:

* Hypothesis property tests (guarded like tests/test_codecs_property.py —
  this container has no hypothesis wheel; CI installs requirements-dev.txt)
  for the host-side pieces: shard padding arithmetic, prefetch plan
  equivalence, and host-tier staging exactness under drawn touch/write
  sequences.
* A 2-device ``(1, 2)`` entity-mesh subprocess sweep asserting the fused
  engine under ``shard_map`` over entity blocks is **bitwise identical**
  to the unsharded fused engine — params, upload history, EF residuals,
  and download counts — over randomized heterogeneous federations and
  every registered codec including error-feedback, plus an end-to-end
  ``run_federated`` trajectory (eval history derives from integer ranks,
  so equality there is rank-exact).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.store import HostTieredStore, _cache_scatter
from repro.core.sync import ROUND_KINDS, insert_prefetch

# ------------------------------------------------------ hypothesis layer
# Guarded per-test (NOT pytest.importorskip at module level) so the
# 2-device mesh smoke below still runs where hypothesis is absent.
try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    from repro.core.eshard import pad_rows

    @settings(max_examples=100, deadline=None)
    @given(
        st.integers(1, 10_000_000),
        st.integers(1, 16),
        st.sampled_from([1, 32]),
    )
    def test_pad_rows_minimal_aligned(n, shards, multiple):
        """pad_rows gives the smallest padded count that splits into equal,
        word-aligned per-shard blocks."""
        p = pad_rows(n, shards, multiple)
        assert p >= n
        assert p % shards == 0
        assert (p // shards) % multiple == 0
        assert p - n < shards * multiple  # minimality

    plan_st = st.lists(
        st.tuples(st.sampled_from(ROUND_KINDS + ("eval",)), st.integers(1, 5)),
        min_size=0, max_size=6,
    )

    @settings(max_examples=100, deadline=None)
    @given(plan_st, st.integers(0, 7))
    def test_insert_prefetch_preserves_rounds(plan, every):
        """Dropping the markers always recovers the original round sequence."""
        plan = tuple(plan)
        out = insert_prefetch(plan, every)
        strip = lambda p: [  # noqa: E731
            k for k, n in p for _ in range(n) if k != "prefetch"
        ]
        assert strip(out) == strip(plan)
        rounds = sum(n for k, n in plan if k in ROUND_KINDS)
        marks = sum(1 for k, _ in out if k == "prefetch")
        if every > 0 and rounds:
            assert marks == -(-rounds // every)

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(7, 30))
    def test_store_staging_exact(seed, h):
        """Host-tier staging == dense shadow for drawn touch/write seqs."""
        rng = np.random.default_rng(seed)
        c_n, e_rows, d, ns_pad = 2, 40, 3, 4
        ent = rng.normal(size=(c_n, e_rows, d)).astype(np.float32)
        shadow = ent.copy()
        store = HostTieredStore(
            ent.copy(), np.zeros_like(ent), np.zeros_like(ent),
            pinned=[np.arange(ns_pad)] * c_n, cache_slots=h, ns_pad=ns_pad,
        )
        cache = store.seed_cache()
        for _ in range(10):
            touched = [
                np.unique(
                    rng.integers(ns_pad, e_rows, size=rng.integers(1, h - ns_pad))
                )
                for _ in range(c_n)
            ]
            cache, slots = store.stage(cache, touched)
            view = np.full((c_n, h - ns_pad), store.h, np.int32)
            for c in range(c_n):
                new = rng.normal(size=(len(touched[c]), d)).astype(np.float32)
                cache = _cache_scatter(
                    cache, np.full(len(slots[c]), c), slots[c], new, new, new
                )
                shadow[c, touched[c]] = new
                view[c, : len(slots[c])] = slots[c]
            store.after_segment(view, np.zeros_like(view, np.float32))
        store.flush(cache)
        np.testing.assert_array_equal(store.ent, shadow)
else:
    @pytest.mark.skip(
        reason="property tests need hypothesis (requirements-dev.txt)"
    )
    def test_hypothesis_properties():
        pass


# --------------------------------------------- 2-device (1, 2) entity mesh
_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys, json, dataclasses
sys.path.insert(0, "src")
import numpy as np
from repro.core.codecs import parse_codec_spec
from repro.core.protocol import build_comm_views
from repro.core.state import CycleEngine
from repro.data import generate_kg, partition_by_relation
from repro.federated.client import KGEClient
from repro.federated.simulation import FederatedConfig, run_federated
from repro.launch.mesh import make_federation_mesh

def instance(seed):
    rng = np.random.default_rng(seed)
    kg = generate_kg(
        num_entities=int(rng.integers(90, 140)),
        num_relations=int(rng.integers(4, 8)),
        num_triples=int(rng.integers(450, 700)),
        seed=seed,
    )
    cd = partition_by_relation(kg, int(rng.integers(2, 4)), seed=seed)
    # heterogeneity: ragged triple counts -> ragged batches-per-epoch
    cd[0] = dataclasses.replace(
        cd[0], train=cd[0].train[: max(40, len(cd[0].train) // 2)]
    )
    views = build_comm_views([d.local_to_global for d in cd], kg.num_entities)
    def mk():
        return [KGEClient(d, method="transe", dim=8, batch_size=24,
                          num_negatives=4, lr=5e-3, seed=seed) for d in cd]
    return kg, cd, views, mk

mesh = make_federation_mesh(1, entity_devices=2)
out = {"engine": {}, "sim": {}}
SPECS = ["identity", "int8", "int8:ef=1", "lowrank", "lowrank:ef=1", "topk-dims"]
for i, spec in enumerate(SPECS):
    seed = 100 + i
    kg, cd, views, mk = instance(seed)
    codec = parse_codec_spec(spec)
    # unsharded on a one-device client mesh: the masked scan, which the
    # entity mesh runs too (with no mesh the trainer runs by segments, which
    # can differ from it in the last bit; tests/test_state.py bounds that)
    host = CycleEngine(mk(), views, kg.num_entities, sparsity_p=0.5,
                       local_epochs=1, codec=codec,
                       mesh=make_federation_mesh(1))
    shrd = CycleEngine(mk(), views, kg.num_entities, sparsity_p=0.5,
                       local_epochs=1, codec=codec,
                       mesh=mesh, entity_axis="entities")
    sh, sp = host.init_state(mk(), seed=7), shrd.init_state(mk(), seed=7)
    ok = True
    for sync in (False, False, True, False):
        sh, dh, lh = host.fused_cycle(sh, sync=sync)
        sp, dp, lp = shrd.fused_cycle(sp, sync=sync)
        ok &= np.array_equal(np.asarray(dh), np.asarray(dp))
        ok &= np.array_equal(np.asarray(lh), np.asarray(lp))
    eh = np.asarray(sh.arrays.params["entity"])
    ep = np.asarray(sp.arrays.params["entity"])[:, : eh.shape[1]]
    ok &= np.array_equal(eh, ep)
    ok &= np.array_equal(np.asarray(sh.arrays.hist),
                         np.asarray(sp.arrays.hist)[:, : sh.arrays.hist.shape[1]])
    if codec.has_residual:
        ok &= np.array_equal(np.asarray(sh.arrays.res),
                             np.asarray(sp.arrays.res)[:, : sh.arrays.res.shape[1]])
    out["engine"][spec] = bool(ok)

# end-to-end trajectory incl. device-resident eval (integer-rank exact)
kg = generate_kg(num_entities=120, num_relations=6, num_triples=800, seed=1)
cd = partition_by_relation(kg, 2, seed=1)
base = dict(method="transe", protocol="feds", dim=8, rounds=7, local_epochs=1,
            batch_size=32, num_negatives=4, lr=5e-3, sparsity_p=0.5,
            codec="int8:ef=1", sync_interval=3, eval_every=3,
            max_eval_triples=64, seed=3)
for engine in ("superstep", "fused"):
    r0 = run_federated(cd, kg.num_entities, FederatedConfig(engine=engine, **base))
    r1 = run_federated(cd, kg.num_entities,
                       FederatedConfig(engine=engine, mesh_entities=2, **base))
    out["sim"][engine] = bool(
        r0.eval_history == r1.eval_history
        and r0.test_mrr_cg == r1.test_mrr_cg
        and r0.test_hits10_cg == r1.test_hits10_cg
        and r0.ledger.params_transmitted == r1.ledger.params_transmitted
    )
print(json.dumps(out))
"""


# ------------------------- 2-device entity-mesh eval exactness (bilinear)
_EVAL_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys, json
sys.path.insert(0, "src")
import numpy as np
from repro.core.evaluation import BatchedEvaluator
from repro.core.protocol import build_comm_views
from repro.core.state import CycleEngine
from repro.data import generate_kg, partition_by_relation
from repro.federated.client import KGEClient
from repro.launch.mesh import make_federation_mesh

mesh = make_federation_mesh(1, entity_devices=2)
out = {}
for i, method in enumerate(("complex", "distmult")):
    seed = 200 + i
    rng = np.random.default_rng(seed)
    kg = generate_kg(num_entities=110, num_relations=6, num_triples=600,
                     seed=seed)
    cd = partition_by_relation(kg, 2, seed=seed)
    views = build_comm_views([d.local_to_global for d in cd], kg.num_entities)
    def mk():
        return [KGEClient(d, method=method, dim=8, batch_size=32,
                          num_negatives=4, lr=5e-3, seed=seed) for d in cd]
    clients = mk()
    host = CycleEngine(clients, views, kg.num_entities, sparsity_p=0.5,
                       local_epochs=1)
    shrd = CycleEngine(mk(), views, kg.num_entities, sparsity_p=0.5,
                       local_epochs=1, mesh=mesh, entity_axis="entities")
    sh, sp = host.init_state(mk(), seed=9), shrd.init_state(mk(), seed=9)
    for sync in (False, True):
        sh, _, _ = host.fused_cycle(sh, sync=sync)
        sp, _, _ = shrd.fused_cycle(sp, sync=sync)
    host.sync_clients(sh, clients)  # numpy-oracle tables

    cap = int(rng.integers(5, 50))
    chunk = int(rng.choice([7, 64]))
    ev = BatchedEvaluator(cd, method=method, gamma=clients[0].gamma,
                          e_max=shrd.e_max, max_triples=cap, chunk=chunk,
                          mesh=mesh, entity_axis="entities")
    ok = True
    for split in ("valid", "test"):
        rt, rh = ev.ranks(sp.arrays.params, split)
        for c, cl in enumerate(clients):
            oracle = cl.ranks(split, cap)  # (n, 2) tail/head integer ranks
            n = oracle.shape[0]
            ok &= bool(np.array_equal(oracle[:, 0], np.asarray(rt)[c, :n]))
            ok &= bool(np.array_equal(oracle[:, 1], np.asarray(rh)[c, :n]))
    out[method] = ok
print(json.dumps(out))
"""


def test_entity_sharded_eval_ranks_match_oracle_bilinear():
    """Bilinear-family eval exactness on the (1, 2) entity mesh: integer
    filtered ranks from the sharded BatchedEvaluator (each shard scans its
    own candidate block, beat counts psum) EXACTLY equal the per-client
    numpy-oracle ranks for complex and distmult, after real training."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_PALLAS_INTERPRET", None)  # exactness pins the ref dispatch
    res = subprocess.run(
        [sys.executable, "-c", _EVAL_WORKER], capture_output=True, text=True,
        env=env, timeout=1800,
        cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out == {"complex": True, "distmult": True}, out


def test_entity_sharded_bitwise_two_devices():
    """(1, 2) entity mesh over 2 fake CPU devices: every registered codec
    (incl. ef) bitwise-equal to unsharded, and end-to-end trajectories with
    eval boundaries identical."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", _WORKER], capture_output=True, text=True,
        env=env, timeout=1800,
        cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert all(out["engine"].values()), out["engine"]
    assert all(out["sim"].values()), out["sim"]


def test_entity_mesh_refused_on_tpu(monkeypatch):
    """On TPU the entity-sharded engine trains to NaN (ROADMAP B0), so
    ``run_federated`` refuses ``mesh_entities > 1`` there before building
    anything."""
    import jax

    from repro.data import generate_kg, partition_by_relation
    from repro.federated.simulation import FederatedConfig, run_federated

    kg = generate_kg(num_entities=60, num_relations=4, num_triples=300, seed=0)
    cd = partition_by_relation(kg, 2, seed=0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="mesh_entities > 1 is refused on TPU"):
        run_federated(cd, kg.num_entities,
                      FederatedConfig(mesh_entities=2, rounds=1, dim=8))
