"""The one file of the benchmark that touches the engine's API.

``run_federated`` has no entry that runs until a deadline, so the
benchmark's window drives the engine that ``run_federated`` builds.  This
adapter builds it with the same public constructors, in the same order, as
``federated/simulation.py: _run_federated_impl`` (``KGEClient``,
``build_comm_views``, ``parse_codec_spec``, the engine class, ``init_state``,
``BatchedEvaluator``) and steps it with the same calls: ``fused_cycle`` per
round with the ISM kind from ``round_kind``, and at an eval boundary the
deferred ledger flush and ``BatchedEvaluator.evaluate``.  Nothing blocks
between rounds, as in ``run_federated``.  If a refactor renames any of
these, this file is the one to re-point.
"""
from __future__ import annotations

import jax

from repro.core.evaluation import BatchedEvaluator
from repro.core.codecs import parse_codec_spec
from repro.core.protocol import build_comm_views
from repro.core.state import CycleEngine
from repro.core.sync import round_kind
from repro.data.partition import ClientData
from repro.federated.client import KGEClient
from repro.federated.comm import CommLedger
from repro.federated.simulation import FederatedConfig, _flush_ledger


def client_data(splits, num_relations: int) -> list[ClientData]:
    """The program's per-client records of the benchmark's partition."""
    return [
        ClientData(client_id=c, train=s.train, valid=s.valid, test=s.test,
                   local_to_global=s.local_to_global,
                   num_relations=num_relations)
        for c, s in enumerate(splits)
    ]


def federated_config(cfg: dict, seed: int, max_eval_triples: int) -> FederatedConfig:
    """``FederatedConfig`` of a benchmark configuration file and a run seed."""
    if cfg["engine"] != "fused":
        raise ValueError(f"the window drives the fused engine, not {cfg['engine']!r}")
    return FederatedConfig(
        method=cfg["method"], protocol=cfg["protocol"], dim=cfg["dim"],
        local_epochs=cfg["local_epochs"], batch_size=cfg["batch_size"],
        num_negatives=cfg["num_negatives"], lr=cfg["lr"],
        adversarial_temperature=cfg["adversarial_temperature"],
        gamma=cfg["gamma"], sparsity_p=cfg["sparsity_p"], codec=cfg["codec"],
        engine=cfg["engine"], sync_interval=cfg["sync_interval"],
        max_eval_triples=max_eval_triples, seed=seed,
    )


class Federation:
    """A federation as ``run_federated`` builds it, stepped a round at a time."""

    def __init__(self, datas: list[ClientData], num_entities: int,
                 fc: FederatedConfig, splits=("valid", "test")):
        self.fc = fc
        clients = [
            KGEClient(d, method=fc.method, dim=fc.dim, gamma=fc.gamma,
                      batch_size=fc.batch_size,
                      num_negatives=fc.num_negatives, lr=fc.lr,
                      adversarial_temperature=fc.adversarial_temperature,
                      seed=fc.seed)
            for d in datas
        ]
        self.views = build_comm_views(
            [d.local_to_global for d in datas], num_entities
        )
        self.codec = parse_codec_spec(fc.codec)
        self.ledger = CommLedger()
        self.engine = CycleEngine(
            clients, self.views, num_entities, sparsity_p=fc.sparsity_p,
            local_epochs=fc.local_epochs, codec=self.codec,
        )
        self.state = self.engine.init_state(clients, seed=fc.seed + 777)
        self.pending: list = []
        self.evaluator = BatchedEvaluator(
            datas, method=fc.method, gamma=fc.gamma, e_max=self.engine.e_max,
            max_triples=fc.max_eval_triples, splits=splits,
            known=[c._known for c in clients],
        )
        self.t = 0

    @property
    def params(self) -> dict:
        return self.state.arrays.params

    @property
    def mu(self) -> dict:
        return self.state.arrays.opt.mu

    def kind(self, t: int) -> str:
        return round_kind(t, self.fc.protocol, self.fc.sync_interval)

    def round(self):
        """One fused train+communicate round.  Returns its device
        ``(down_count (C,), loss (C,))``; nothing is read back."""
        kind = self.kind(self.t)
        with jax.profiler.TraceAnnotation("dispatch"):
            self.state, down, loss = self.engine.fused_cycle(
                self.state, sync=kind == "sync", t=self.t
            )
        self.pending.append((kind, down if kind == "sparse" else None, self.t, None))
        self.t += 1
        return down, loss

    def flush_ledger(self) -> None:
        with jax.profiler.TraceAnnotation("ledger_readback"):
            _flush_ledger(self.ledger, self.pending, self.views, self.codec,
                          self.fc.dim, self.engine.k_per_client)

    def evaluate(self, split: str):
        """The ``(C, 5)`` metric block of the live tables, read back to the
        host."""
        with jax.profiler.TraceAnnotation("eval_readback"):
            return self.evaluator.evaluate(self.params, split)

    def eval_boundary(self, split: str):
        """What ``run_federated`` does at an eval boundary, less the
        bookkeeping of the best round: the ledger flushed and the ``(C, 5)``
        metric block of the live tables read back."""
        self.flush_ledger()
        return self.evaluate(split)
