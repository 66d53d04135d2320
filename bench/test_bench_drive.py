"""The benchmark's graph, and the adapter the window drives, against ``run_federated``."""
import dataclasses
import time

import numpy as np
import pytest

import drive
import kgdata
from repro.federated.metrics import aggregate_eval_block
from repro.federated.simulation import run_federated

FB15K237 = dict(num_entities=14541, num_relations=237, num_triples=310116,
                num_clusters=12, zipf_a=1.3, graph_seed=0)


def test_graph_has_the_stated_shape():
    t0 = time.perf_counter()
    tri = kgdata.generate_graph(14541, 237, 310116, 12, 1.3, seed=0)
    took = time.perf_counter() - t0
    assert tri.shape == (310116, 3) and tri.dtype == np.int32
    assert len(np.unique(tri, axis=0)) == tri.shape[0]
    assert not (tri[:, 0] == tri[:, 2]).any()
    assert tri[:, [0, 2]].min() >= 0 and tri[:, [0, 2]].max() < 14541
    assert tri[:, 1].min() >= 0 and tri[:, 1].max() < 237
    counts = np.sort(np.bincount(tri[:, 1], minlength=237))[::-1]
    # Zipf(1.3): the top relation holds about a third of the triples and
    # the counts fall with rank; the median relation holds well under 1%
    assert 0.2 < counts[0] / tri.shape[0] < 0.45
    assert counts[0] > 2 * counts[1] > 0 and counts[118] < 0.01 * tri.shape[0]
    assert took < 5.0
    again = kgdata.generate_graph(14541, 237, 310116, 12, 1.3, seed=0)
    assert (again == tri).all()


def test_partition_covers_the_graph_once():
    ds = dict(FB15K237, num_entities=500, num_relations=20, num_triples=6000,
              num_clusters=5)
    tri = kgdata.generate_graph(500, 20, 6000, 5, 1.3, 0)
    clients = kgdata.partition_by_relation(tri, 500, 20, 3, 0)
    back = np.concatenate([
        np.stack([c.local_to_global[x[:, 0]], x[:, 1], c.local_to_global[x[:, 2]]], 1)
        for c in clients for x in (c.train, c.valid, c.test)
    ])
    assert sorted(map(tuple, back)) == sorted(map(tuple, tri))
    rel_sets = [set(np.concatenate([c.train, c.valid, c.test])[:, 1]) for c in clients]
    assert not (rel_sets[0] & rel_sets[1]) and not (rel_sets[1] & rel_sets[2])
    assert [len(c.train) for c in clients] == [
        len(c.train) for c in kgdata.federation(ds, 3)]


def tiny(method="transe"):
    ds = dict(FB15K237, num_entities=200, num_relations=12, num_triples=3000,
              num_clusters=4)
    cfg = {"dataset": ds, "num_clients": 3, "method": method, "dim": 16,
           "batch_size": 64, "num_negatives": 4, "gamma": 8.0,
           "adversarial_temperature": 1.0, "lr": 0.01, "sparsity_p": 0.4,
           "sync_interval": 4, "codec": "identity", "protocol": "feds",
           "engine": "fused", "local_epochs": 1}
    return cfg, kgdata.federation(ds, 3)


@pytest.mark.parametrize("method", ["transe", "complex"])
def test_drive_steps_what_run_federated_runs(method):
    cfg, splits = tiny(method)
    datas = drive.client_data(splits, cfg["dataset"]["num_relations"])
    fc = drive.federated_config(cfg, seed=5, max_eval_triples=500)
    span = cfg["sync_interval"] + 1
    fed = drive.Federation(datas, cfg["dataset"]["num_entities"], fc)
    for _ in range(span):
        fed.round()
    val = aggregate_eval_block(fed.eval_boundary("valid"))
    res = run_federated(datas, cfg["dataset"]["num_entities"],
                        dataclasses.replace(fc, rounds=span, eval_every=span))
    assert fed.ledger.history == res.ledger.history
    assert fed.ledger.params_transmitted == res.ledger.params_transmitted
    assert [(span, val["mrr"], val["hits10"])] == res.eval_history


def test_drive_refuses_another_engine():
    cfg, _ = tiny()
    with pytest.raises(ValueError):
        drive.federated_config(dict(cfg, engine="superstep"), 0, 500)
