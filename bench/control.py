"""Readings that set the limits of a cell's output check, at the cell's size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--program]

For each seed, the control (the reference run in bfloat16, put in the
program's place) and each planted fault the cell can have are compared
with the float32 reference by the numbers the check compares; one JSON
line per seed and reading.  With ``--program`` (train cells) the reading
is instead the program's own first span, driven as a run's set-up drives
it, against the reference: the sound runs that set the lower readings,
many seeds in one process.  The benchmark's own runs never run this.

Faults read here: train cells, half of each batch left out of the loss
(the mean over the rest) and the sync round run as a sparse round; eval
cells, one answer altered where it is produced (one query's tail rank set
to 1).  A state left unchanged reads 1 on ``change_gap`` by the measure's
definition and needs no run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import check  # noqa: E402
import harness  # noqa: E402
import kgdata  # noqa: E402
import reference  # noqa: E402


def train_readings(cfg, traffic, splits, seed) -> dict:
    ref = harness.reference_train(cfg, splits, seed, traffic)
    rows = harness.leaf_rows(cfg, splits)
    out = {}
    for name, kw in (("control_bf16", {"dtype": jnp.bfloat16}),
                     ("fault_half_batch", {"half_batch": True}),
                     ("fault_sync_as_sparse", {"sync_as_sparse": True})):
        got = harness.reference_train(cfg, splits, seed, traffic, **kw)
        out[name] = check.train_numbers(got, ref, rows)
    return out


def program_readings(cfg, traffic, splits, seed) -> dict:
    prog = harness.program_train(cfg, traffic, splits, seed)
    ref = harness.reference_train(cfg, splits, seed, traffic)
    return {"program": check.train_numbers(prog, ref, harness.leaf_rows(cfg, splits))}


def altered_block(cfg, splits, seed, split, max_triples) -> np.ndarray:
    """The reference's block with one answer altered: the first query's
    tail rank set to 1."""
    out = []
    for c, s in enumerate(splits):
        p = reference.init_tables(seed, c, s.num_entities,
                                  cfg["dataset"]["num_relations"], cfg["dim"],
                                  cfg["gamma"], jnp.float32, cfg["method"])
        rows = getattr(s, split)[:max_triples]
        ranks = reference.filtered_ranks(
            p, rows, np.concatenate([s.train, s.valid, s.test]),
            cfg["method"], cfg["gamma"])
        if c == 0:
            ranks[0, 0] = 1
        out.append(reference.metric_block(ranks))
    return np.stack(out)


def eval_readings(cfg, traffic, splits, seed) -> dict:
    split, n = traffic["eval_split"], traffic["max_eval_triples"]
    ref = harness.reference_eval(cfg, splits, seed, traffic, split)
    ctrl = harness.reference_eval(cfg, splits, seed, traffic, split,
                                  dtype=jnp.bfloat16)
    return {
        "control_bf16": check.eval_numbers([ctrl], ref),
        "fault_altered_answer": check.eval_numbers(
            [altered_block(cfg, splits, seed, split, n)], ref),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true",
                    help="read the program's sound runs instead (train cells)")
    args = ap.parse_args()
    w, cfg, traffic, _, _, _ = harness.cell(harness.CHECKOUT, args.workload)
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    splits = kgdata.federation(cfg["dataset"], cfg["num_clients"])
    if args.program:
        readings = program_readings
    else:
        readings = train_readings if traffic["kind"] == "train" else eval_readings
    for seed in (int(s) for s in args.seeds.split(",")):
        for name, numbers in readings(cfg, traffic, splits, seed).items():
            print(json.dumps({"workload": w["name"], "seed": seed,
                              "reading": name, **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
