"""The benchmark's FB15k-237-shaped knowledge graph, made in bulk with numpy.

FB15k-237 (Toutanova & Chen, 2015) has 14,541 entities, 237 relations and
310,116 triples.  The repository ships no copy of it, so the benchmark makes a
graph of that shape, with the structure the repository's own generator
(``repro.data.synthetic.generate_kg``) gives it: entities fall into soft
clusters, every relation links a source cluster to a target cluster with a
little uniform noise, and relation frequencies follow a Zipf law.  This
copy draws every triple at once instead of one per Python iteration.

The graph is then dealt to clients by relation, as the FedS paper builds
FB15k-237-R3/R5/R10 (arXiv:2406.13225): relations are shuffled and dealt
round-robin, each client takes every triple of its relations, relabels
its entities densely, and splits its triples 0.8/0.1/0.1.

The graph and the partition come from the configuration's own
``graph_seed``, never from a run's ``--seed``: like the real dataset, every
run trains on the same graph, and the run's seed drives the model.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class ClientSplit:
    """One client's view: triples in local entity ids, global relation ids."""

    train: np.ndarray  # (T, 3) int32
    valid: np.ndarray
    test: np.ndarray
    local_to_global: np.ndarray  # (E_c,) int32

    @property
    def num_entities(self) -> int:
        return int(self.local_to_global.shape[0])


def generate_graph(
    num_entities: int,
    num_relations: int,
    num_triples: int,
    num_clusters: int,
    zipf_a: float,
    seed: int,
) -> np.ndarray:
    """``(num_triples, 3)`` int32 distinct triples without self-loops."""
    rng = np.random.default_rng(seed)
    cluster_of = rng.integers(0, num_clusters, size=num_entities)
    order = np.argsort(cluster_of, kind="stable")  # members, cluster by cluster
    size = np.bincount(cluster_of, minlength=num_clusters)
    if (size == 0).any():
        raise ValueError("an entity cluster is empty; use fewer clusters")
    start = np.concatenate([[0], np.cumsum(size)[:-1]])
    rel_src = rng.integers(0, num_clusters, size=num_relations)
    rel_dst = rng.integers(0, num_clusters, size=num_relations)
    rel_noise = rng.uniform(0.05, 0.25, size=num_relations)
    probs = np.arange(1, num_relations + 1, dtype=np.float64) ** (-zipf_a)
    probs /= probs.sum()

    keys = np.zeros(0, np.int64)
    need = num_triples
    while True:
        n = 2 * need + 1024
        r = rng.choice(num_relations, size=n, p=probs)
        noisy = rng.random(n) < rel_noise[r]
        src, dst = rel_src[r], rel_dst[r]
        h = order[start[src] + (rng.random(n) * size[src]).astype(np.int64)]
        t = order[start[dst] + (rng.random(n) * size[dst]).astype(np.int64)]
        h = np.where(noisy, rng.integers(0, num_entities, size=n), h)
        t = np.where(noisy, rng.integers(0, num_entities, size=n), t)
        ok = h != t
        new = (h[ok] * num_relations + r[ok]) * num_entities + t[ok]
        keys = np.concatenate([keys, new])
        _, first = np.unique(keys, return_index=True)
        keys = keys[np.sort(first)]  # distinct, in order of first draw
        if keys.shape[0] >= num_triples:
            keys = keys[:num_triples]
            break
        need = num_triples - keys.shape[0]
    t = keys % num_entities
    hr = keys // num_entities
    return np.stack([hr // num_relations, hr % num_relations, t], 1).astype(
        np.int32
    )


def partition_by_relation(
    triples: np.ndarray,
    num_entities: int,
    num_relations: int,
    num_clients: int,
    seed: int,
    ratios: tuple = (0.8, 0.1, 0.1),
) -> list[ClientSplit]:
    """Deal relations round-robin after a seeded shuffle; split per client."""
    rng = np.random.default_rng(seed)
    owner = np.empty(num_relations, np.int64)
    owner[rng.permutation(num_relations)] = np.arange(num_relations) % num_clients
    out = []
    for c in range(num_clients):
        mine = triples[owner[triples[:, 1]] == c]
        if mine.shape[0] == 0:
            raise ValueError(f"client {c} received no triples")
        ents = np.unique(mine[:, [0, 2]])
        remap = np.full(num_entities, -1, np.int32)
        remap[ents] = np.arange(ents.shape[0], dtype=np.int32)
        local = np.stack(
            [remap[mine[:, 0]], mine[:, 1], remap[mine[:, 2]]], 1
        ).astype(np.int32)
        idx = rng.permutation(local.shape[0])
        n_tr = max(1, int(local.shape[0] * ratios[0]))
        n_va = max(1, int(local.shape[0] * ratios[1]))
        out.append(ClientSplit(
            train=local[idx[:n_tr]],
            valid=local[idx[n_tr:n_tr + n_va]],
            test=local[idx[n_tr + n_va:]],
            local_to_global=ents.astype(np.int32),
        ))
    return out


def federation(dataset: dict, num_clients: int) -> list[ClientSplit]:
    """The clients of a configuration's ``dataset`` block."""
    triples = generate_graph(
        dataset["num_entities"], dataset["num_relations"],
        dataset["num_triples"], dataset["num_clusters"], dataset["zipf_a"],
        dataset["graph_seed"],
    )
    return partition_by_relation(
        triples, dataset["num_entities"], dataset["num_relations"],
        num_clients, dataset["graph_seed"],
    )
