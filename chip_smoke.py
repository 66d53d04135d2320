#!/usr/bin/env python3
"""Run the FedS training path on a TPU and check what comes out.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one host with four chips

One chip, two phases:

1. kernels: every Pallas kernel of the KGE path, at D=256, against its
   jnp reference (``kernels/ref.py``, the ``kge/scoring.py`` broadcast) on
   the same inputs, within the tolerances of ``tests/test_kernels.py``;
2. training: an FB15k-237-shaped federation (14,541 entities, 237
   relations, 310,116 triples) split by relation over 3 clients (R3), run
   through ``run_federated`` at ``FederatedConfig``'s paper widths for 5
   rounds (4 sparse, 1 sync, 1 eval boundary), once with the ``fused``
   engine and once with ``superstep``.  JAX writes each program it hands
   to its compiler (or looks up in the compile cache) as StableHLO to
   ``.jax_ir/``; the script reads there which programs call which Pallas
   kernels (``tpu_custom_call``), so the proof covers the very programs
   ``run_federated`` ran.

``--four-chips`` runs instead the FB15k-237-shaped R10 federation on a
``(clients=2, entities=2)`` mesh and the same configuration unsharded on
one chip of that host, and compares the two.

The script exits non-zero unless JAX's first device is a TPU; it never
falls back to the CPU or to the reference kernels, and any failed check
exits non-zero.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import os
import re
import shutil
import sys
import time

CHECKOUT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(CHECKOUT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.data import generate_kg, partition_by_relation  # noqa: E402
from repro.federated.simulation import FederatedConfig, run_federated  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.change_score import change_score_pallas  # noqa: E402
from repro.kernels.sparse_apply import sparse_apply_pallas  # noqa: E402
from repro.kge.scoring import get_scoring  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

# FB15k-237 (Toutanova & Chen, 2015), the paper's dataset, as a synthetic
# graph of the same shape
FB15K237 = dict(num_entities=14541, num_relations=237, num_triples=310116)
# the Pallas kernel functions (the ``kernel_name`` of their
# ``tpu_custom_call``) and the names this script reports them by
KERNELS = {"_change_score_kernel": "change_score",
           "_sparse_apply_kernel": "sparse_apply",
           "_dist_cand_kernel": "dist_cand_score",
           "_transe_mxu_kernel": "dist_cand_score",
           "_bilinear_kernel": "bilinear_cand_score"}
IR_DIR = os.path.join(CHECKOUT, ".jax_ir")


class SmokeFailure(Exception):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(**kv) -> None:
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


@contextlib.contextmanager
def programs():
    """Yields a list that, once the block ends, holds ``(name, kernels)``
    for every program compiled (or fetched from the compile cache) inside
    the block: its module name and the Pallas kernels it calls.  Names
    repeat (a pod engine's sparse and sync programs are both
    ``jit_fused``)."""
    shutil.rmtree(IR_DIR, ignore_errors=True)
    found: list[tuple[str, list[str]]] = []
    jax.config.update("jax_include_debug_info_in_dumps", False)
    jax.config.update("jax_dump_ir_to", IR_DIR)
    try:
        yield found
    finally:
        jax.config.update("jax_dump_ir_to", "")
    names = sorted(os.listdir(IR_DIR)) if os.path.isdir(IR_DIR) else []
    for name in names:
        m = re.fullmatch(r"jax_ir\d+_(.+)_compile\.mlir", name)
        if m is None:
            continue
        with open(os.path.join(IR_DIR, name)) as f:
            calls = [ln for ln in f if "tpu_custom_call" in ln]
        found.append((m.group(1), sorted(
            {short for k, short in KERNELS.items() for ln in calls if k in ln}
        )))
    shutil.rmtree(IR_DIR, ignore_errors=True)


def has(progs, kernels: list[str]) -> bool:
    """Whether one of ``progs`` calls exactly ``kernels``."""
    return any(ks == kernels for _, ks in progs)


class CompileLog:
    """JAX's compile and persistent-cache events, counted since the last
    :meth:`take`."""

    def __init__(self):
        self.events = collections.Counter()
        self.secs = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda name, **kw: self.events.update([name]))
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: self.secs.update({name: secs}))

    def take(self) -> dict:
        cc, comp = "/jax/compilation_cache/", "/jax/core/compile/"
        out = {
            "cache_hits": self.events[cc + "cache_hits"],
            "cache_misses": self.events[cc + "cache_misses"],
            "backend_compile_s": self.secs[comp + "backend_compile_duration"],
            "cache_retrieval_s": self.secs[cc + "cache_retrieval_time_sec"],
            "trace_lower_s": self.secs[comp + "jaxpr_trace_duration"]
            + self.secs[comp + "jaxpr_to_mlir_module_duration"],
        }
        self.events.clear()
        self.secs.clear()
        return out


def close(name, got, want, rtol, atol) -> None:
    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
    err = float(np.max(np.abs(got - want)))
    ok = bool(np.all(np.abs(got - want) <= atol + rtol * np.abs(want)))
    say(phase="kernels", kernel=name, shape=list(got.shape),
        max_abs_err=err, within_tol=ok)
    check(ok, f"{name}: kernel differs from its reference (max |err| {err})")


# ----------------------------------------------------------------- kernels
def kernel_phase(seed: int) -> None:
    """Each kernel on the chip vs its reference, at round/eval shapes."""
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    d = 256
    n = 15104  # a flattened (C * Ns_max, D) round table
    cur = jax.random.normal(next(ks), (n, d))
    hist = jax.random.normal(next(ks), (n, d)) * 0.5 + cur
    with programs() as progs:
        got = change_score_pallas(cur, hist)
    check(has(progs, ["change_score"]), "change_score: no Pallas kernel")
    close("change_score", got, ref.change_score_ref(cur, hist), 1e-5, 1e-5)

    emb = jax.random.normal(next(ks), (n, d))
    agg = jax.random.normal(next(ks), (n, d))
    pri = jax.random.randint(next(ks), (n,), 0, 5).astype(jnp.float32)
    sign = (jax.random.uniform(next(ks), (n,)) < 0.4).astype(jnp.int8)
    with programs() as progs:
        got = sparse_apply_pallas(emb, agg, pri, sign)
    check(has(progs, ["sparse_apply"]), "sparse_apply: no Pallas kernel")
    close("sparse_apply", got, ref.sparse_apply_ref(emb, agg, pri, sign),
          1e-5, 1e-6)

    # the eval's candidate scan: (C, B, D) queries x (C, chunk, D) rows
    c, b, chunk = 3, 500, 512
    for method, kernel in (
        ("transe", "dist_cand_score"),
        ("rotate", "dist_cand_score"),
        ("protate", "dist_cand_score"),
        ("distmult", "bilinear_cand_score"),
        ("complex", "bilinear_cand_score"),
    ):
        spec = get_scoring(method)
        h = jax.random.normal(next(ks), (c, b, d))
        r = jax.random.normal(next(ks), (c, b, spec.rel_dim(d)))
        t = jax.random.normal(next(ks), (c, b, d))
        cand = jax.random.normal(next(ks), (c, chunk, d))
        fn = jax.jit(lambda *a, m=method: ops.kge_cand_scores(*a, m, 8.0))
        with programs() as progs:
            ts, hs = fn(h, r, t, cand)
        check(has(progs, [kernel]), f"{method}: no {kernel}")
        hq, rq = h[..., :, None, :], r[..., :, None, :]
        close(f"{method}_tail", ts,
              spec.score(hq, rq, cand[..., None, :, :], 8.0), 1e-4, 1e-4)
        close(f"{method}_head", hs,
              spec.score(cand[..., None, :, :], rq, t[..., :, None, :], 8.0),
              1e-4, 1e-4)


# ---------------------------------------------------------------- training
def smoke_config(seed: int) -> FederatedConfig:
    """Paper widths (``FederatedConfig`` defaults: TransE, D=256, B=512,
    N=64, p=0.4, s=4) for 5 rounds of 1 local epoch: 4 sparse rounds, 1
    sync round and 1 eval boundary.  The learning rate is
    ``launch/train.py``'s default; at ``FederatedConfig``'s 1e-4, five
    epochs leave the test MRR below chance."""
    return FederatedConfig(local_epochs=1, rounds=5, eval_every=5, lr=1e-2,
                           seed=seed)


def federation(num_clients: int, seed: int):
    t0 = time.perf_counter()
    kg = generate_kg(**FB15K237, seed=seed)
    cd = partition_by_relation(kg, num_clients, seed=seed)
    say(phase="data", clients=num_clients, entities=kg.num_entities,
        relations=kg.num_relations, triples=kg.num_triples,
        train_per_client=[c.num_train for c in cd],
        wall_s=time.perf_counter() - t0)
    return kg, cd


def run(cd, num_entities, cfg, label, log: CompileLog) -> object:
    """One ``run_federated`` call; its round programs must call the Pallas
    kernels."""
    t0 = time.perf_counter()
    with programs() as progs:
        res = run_federated(cd, num_entities, cfg)
    wall = time.perf_counter() - t0
    with_kernels = [(name, ks) for name, ks in progs if ks]
    say(phase="train", run=label, rounds=res.rounds_run, wall_s=wall,
        eval_history=json.dumps(res.eval_history), test_mrr=res.test_mrr_cg,
        test_hits10=res.test_hits10_cg,
        params_transmitted=res.ledger.params_transmitted,
        programs=json.dumps(with_kernels), **log.take())
    need = {"change_score", "sparse_apply"}
    if cfg.engine == "superstep":  # the eval runs inside the round program
        need.add("dist_cand_score")
    check(any(need <= set(ks) for _, ks in with_kernels),
          f"{label}: no program calls all of {sorted(need)}")
    check(any("dist_cand_score" in ks for _, ks in with_kernels),
          f"{label}: no eval program calls dist_cand_score")
    return res


def check_result(res, cfg, num_entities: int, label: str) -> None:
    check(res.rounds_run == cfg.rounds, f"{label}: stopped at {res.rounds_run}")
    check([r for r, _, _ in res.eval_history] == [cfg.rounds],
          f"{label}: eval boundaries {res.eval_history}")
    # a NaN or constant table ranks every gold triple first, so finiteness
    # alone is no guard: each MRR must beat chance and stay far below a
    # perfect score
    floor = 3.0 * chance_mrr(num_entities)
    for split, mrr in [("valid", m) for _, m, _ in res.eval_history] + [
            ("test", res.test_mrr_cg)]:
        check(floor < mrr < 0.5,
              f"{label}: {split} MRR {mrr} outside ({floor}, 0.5)")


def chance_mrr(num_entities: int) -> float:
    """MRR of ranks drawn uniformly from 1..E."""
    return float(np.sum(1.0 / np.arange(1, num_entities + 1)) / num_entities)


def compare(a, b, label: str) -> None:
    """The ledgers must agree exactly.  Eval numbers are printed with their
    difference: bitwise agreement is the CPU contract, and a chip that
    breaks it is reported, not failed."""
    pa, pb = a.ledger.params_transmitted, b.ledger.params_transmitted
    say(phase="compare", what=label, params_equal=pa == pb,
        ledger_history_equal=a.ledger.history == b.ledger.history,
        test_mrr_bitwise=a.test_mrr_cg == b.test_mrr_cg,
        test_mrr_diff=a.test_mrr_cg - b.test_mrr_cg,
        test_hits10_diff=a.test_hits10_cg - b.test_hits10_cg,
        eval_history_bitwise=a.eval_history == b.eval_history,
        val_mrr_diff=[x[1] - y[1] for x, y in zip(a.eval_history,
                                                   b.eval_history)])
    diff = [r for (r, x), (_, y) in zip(a.ledger.history, b.ledger.history)
            if x != y]
    say(phase="compare", what=label,
        ledger_rounds_differing=diff, ledger_a=json.dumps(a.ledger.history),
        ledger_b=json.dumps(b.ledger.history))
    check(pa == pb and float(pa).is_integer(),
          f"{label}: params_transmitted {pa} != {pb}")
    check(a.ledger.history == b.ledger.history, f"{label}: ledger histories")


def one_chip(seed: int, log: CompileLog) -> None:
    t0 = time.perf_counter()
    kernel_phase(seed)
    say(phase="kernels", wall_s=time.perf_counter() - t0, **log.take())
    kg, cd = federation(3, seed)
    cfg = smoke_config(seed)
    sscfg = dataclasses.replace(cfg, engine="superstep")
    fused = run(cd, kg.num_entities, cfg, "R3_fused", log)
    sstep = run(cd, kg.num_entities, sscfg, "R3_superstep", log)
    compare(fused, sstep, "fused_vs_superstep")
    check_result(fused, cfg, kg.num_entities, "R3_fused")
    check_result(sstep, sscfg, kg.num_entities, "R3_superstep")


def four_chips(seed: int, log: CompileLog) -> None:
    check(len(jax.devices()) >= 4, f"--four-chips found {jax.devices()}")
    kg, cd = federation(10, seed)
    cfg = smoke_config(seed)
    mcfg = dataclasses.replace(cfg, mesh_devices=2, mesh_entities=2)
    sharded = run(cd, kg.num_entities, mcfg, "R10_mesh2x2", log)
    single = run(cd, kg.num_entities, cfg, "R10_one_chip", log)
    compare(sharded, single, "mesh2x2_vs_one_chip")
    check_result(sharded, mcfg, kg.num_entities, "R10_mesh2x2")
    check_result(single, cfg, kg.num_entities, "R10_one_chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="R10 on a (2, 2) mesh vs unsharded, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cache = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (first device: {dev.platform}); refusing "
              "to run on anything else", file=sys.stderr)
        return 1
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(phase="start", device=json.dumps(device), jax=jax.__version__,
        compile_cache=cache, cache_entries=n_cached)
    t0 = time.perf_counter()
    try:
        (four_chips if args.four_chips else one_chip)(args.seed, CompileLog())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    n_after = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    say(phase="done", wall_s=time.perf_counter() - t0,
        cache_entries_written=n_after - n_cached)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
