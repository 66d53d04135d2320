"""One run of one benchmark cell: set up, warm up, measure, check, report.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``configs/<config>.json``, its traffic mix in
``traffic/<traffic>.json``, the limits of its output check in
``limits/<cell>.json``, each per-layer metric's reader in
``metrics/<metric>.py`` and the device's peaks in ``peaks.json``.

A reader is a module with ``read(ctx)``, returning the metric or None where
it finds nothing to read.  ``ctx`` holds ``trace`` (a
``tracereduce.Reduced``: the traced window, device busy time, the device
ops cut to the window, and ``op_s(pattern)`` / ``op_calls(pattern)`` for
the ops whose names hold ``pattern``), ``shapes`` (per-client entities,
train triples, shared entities and the triples an eval call ranks),
``work`` (the rounds, sparse and sync rounds and evals, or the calls, in
the traced window), ``cfg`` (the configuration) and ``peak`` (the device's
row of ``peaks.json``).  So a metric of any op, kernel or fusion is one new
file.
"""
from __future__ import annotations

import argparse
import collections
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import check
import costs
import drive
import kgdata
import reference
import tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(CHECKOUT, ".bench", "trace")
# host spans the harness writes around its calls into the engine
HOST_SPANS = ("window", "dispatch", "ledger_readback", "eval_readback")


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def say(stream=sys.stdout, **kv) -> None:
    print(" ".join(f"{k}={v}" for k, v in kv.items()), file=stream, flush=True)


class CompileLog:
    """JAX's compile and compile-cache events, counted since the start."""

    def __init__(self):
        self.events = collections.Counter()
        self.secs = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda name, **kw: self.events.update([name]))
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: self.secs.update({name: secs}))

    def summary(self) -> dict:
        cc, comp = "/jax/compilation_cache/", "/jax/core/compile/"
        return {
            "cache_hits": self.events[cc + "cache_hits"],
            "cache_misses": self.events[cc + "cache_misses"],
            "backend_compile_s": self.secs[comp + "backend_compile_duration"],
            "trace_lower_s": self.secs[comp + "jaxpr_trace_duration"]
            + self.secs[comp + "jaxpr_to_mlir_module_duration"],
        }


def cell(bench_root: str, name: str):
    """The cell's entry, configuration, traffic, limits and per-layer
    metric definitions."""
    spec = load_json(bench_root, "BENCHMARK.json")
    found = [w for w in spec["workloads"] if w["name"] == name]
    if not found:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = found[0]
    cfg = load_json(HERE, "configs", f"{w['config']}.json")
    traffic = load_json(HERE, "traffic", f"{w['traffic']}.json")
    limits = load_json(HERE, "limits", f"{name}.json")
    per_layer = [m for m in spec["per_layer"] if name in m.get("workloads", [name])]
    end_to_end = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    return w, cfg, traffic, limits, end_to_end, per_layer


def reader(metric: str):
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def copy_tree(tree):
    return jax.tree.map(jnp.copy, tree)


def to_host(tree):
    return jax.tree.map(np.asarray, tree)


def memory_peak() -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def traced(fn):
    """Run ``fn`` under the profiler inside a ``window`` span; returns
    ``(fn's result, trace)``."""
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    os.makedirs(TRACE_DIR)
    jax.profiler.start_trace(TRACE_DIR)
    try:
        with jax.profiler.TraceAnnotation("window"):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, tracereduce.load(TRACE_DIR, host_names=set(HOST_SPANS))


def federation(cfg, traffic, seed, splits) -> drive.Federation:
    """The program's federation of the benchmark's clients, with the eval
    bank of the traffic's split."""
    return drive.Federation(
        drive.client_data(splits, cfg["dataset"]["num_relations"]),
        cfg["dataset"]["num_entities"],
        drive.federated_config(cfg, seed, traffic["max_eval_triples"]),
        splits=(traffic["eval_split"],))


def shapes(cfg, traffic, splits) -> dict:
    """Per-client sizes of the cell, from the benchmark's own partition,
    that the metric readers count the logical work from: entities, train
    triples, shared entities (held by two or more clients) and the triples
    of the traffic's split that an eval call ranks."""
    owners = np.zeros(cfg["dataset"]["num_entities"], np.int64)
    for s in splits:
        owners[s.local_to_global] += 1
    split, cap = traffic["eval_split"], traffic["max_eval_triples"]
    return {
        "entities": [int(s.num_entities) for s in splits],
        "relations": int(cfg["dataset"]["num_relations"]),
        "train": [int(s.train.shape[0]) for s in splits],
        "shared": [int((owners[s.local_to_global] >= 2).sum()) for s in splits],
        "queries": [int(min(getattr(s, split).shape[0], cap)) for s in splits],
    }


# ------------------------------------------------------------------ train
def drive_span(fed, span: int, split: str) -> dict:
    """The first ISM span, driven through the window's own calls, with what
    the check compares: each round's loss, the sparse rounds' download
    counts, Adam's first moment after round one, the tables and upload
    history before and after the span, and the eval boundary's block."""
    prog = {"params0": copy_tree(fed.params), "loss": [], "down": [],
            "hist0": jnp.copy(fed.state.arrays.hist)}
    for r in range(span):
        kind = fed.kind(fed.t)
        down, loss = fed.round()
        prog["loss"].append(loss)
        if kind == "sparse":
            prog["down"].append(down)
        if r == 0:
            prog["mu1"] = copy_tree(fed.mu)
    prog["params_end"] = copy_tree(fed.params)
    prog["hist_end"] = jnp.copy(fed.state.arrays.hist)
    prog["eval"] = fed.eval_boundary(split)
    jax.block_until_ready(fed.state.arrays)
    return prog


def span_on_host(prog: dict) -> dict:
    prog = to_host(prog)
    prog["loss"], prog["down"] = np.stack(prog["loss"]), np.stack(prog["down"])
    for name in ("params0", "mu1", "params_end"):
        prog[name] = {k: prog[name][k] for k in check.LEAVES}
    return prog


def ism_span(cfg, traffic) -> int:
    span = traffic["eval_every"]
    if span != cfg["sync_interval"] + 1:
        raise ValueError("a window span is one ISM span: eval_every must be "
                         "sync_interval + 1")
    return span


def run_train(cfg, traffic, seed, seconds, trace, splits, t0, log):
    span, split = ism_span(cfg, traffic), traffic["eval_split"]
    t = time.perf_counter()
    fed = federation(cfg, traffic, seed, splits)
    setup = {"clients_s": time.perf_counter() - t}
    # set-up drives the first span, which the check follows
    t = time.perf_counter()
    prog = drive_span(fed, span, split)
    setup["warm_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t0
    setup.update(log.summary())

    def one_span():
        for _ in range(span):
            fed.round()
        return fed.eval_boundary(split)

    result = {"attempted": 0, "failed": 0}
    reduced = None
    if trace:
        block, tr = traced(one_span)
        rounds = span
        result["attempted"] = 1
        result["failed"] = int(not np.all(np.isfinite(block)))
        reduced = tracereduce.reduce(tr, "window")
    else:
        w0 = time.perf_counter()
        rounds, marks = 0, [w0]
        while True:
            block = one_span()
            rounds += span
            result["attempted"] += 1
            result["failed"] += int(not np.all(np.isfinite(block)))
            marks.append(time.perf_counter())
            if marks[-1] - w0 >= seconds:
                break
        result["round_s"] = (marks[-1] - w0) / rounds
        say(phase="spans", seconds=json.dumps(np.diff(marks).tolist()))
    result["memory_peak_bytes"] = memory_peak()
    say(phase="window", rounds=rounds, evals=result["attempted"],
        params_transmitted=fed.ledger.params_transmitted)
    sync_rounds = sum(fed.kind(t) == "sync" for t in range(span, span + rounds))
    work = {"rounds": rounds, "sparse_rounds": rounds - sync_rounds,
            "sync_rounds": sync_rounds, "evals": result["attempted"]}

    # the check: the program's state is freed before the reference runs
    prog = span_on_host(prog)
    del fed
    gc.collect()
    t = time.perf_counter()
    ref = reference_train(cfg, splits, seed, traffic)
    ref_s = time.perf_counter() - t
    numbers = check.train_numbers(prog, ref, leaf_rows(cfg, splits))
    return setup_s, setup, result, reduced, numbers, work, ref_s


def leaf_rows(cfg, splits) -> list:
    return [{"entity": s.num_entities, "relation": cfg["dataset"]["num_relations"]}
            for s in splits]


def program_train(cfg, traffic, splits, seed) -> dict:
    """The program's first span from the seed, on the host, its state freed."""
    fed = federation(cfg, traffic, seed, splits)
    prog = span_on_host(drive_span(fed, ism_span(cfg, traffic), traffic["eval_split"]))
    del fed
    gc.collect()
    return prog


def reference_train(cfg, splits, seed, traffic, dtype=jnp.float32,
                    half_batch=False, sync_as_sparse=False):
    """The reference's first ISM span and the eval of its last tables."""
    rcfg = dict(cfg, num_relations=cfg["dataset"]["num_relations"])
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        fed = reference.Federation(splits, cfg["dataset"]["num_entities"], rcfg,
                                   seed, dtype=dtype, half_batch=half_batch,
                                   sync_as_sparse=sync_as_sparse)
        out = {"params0": fed.params0, "loss": [], "down": []}
        for r in range(ism_span(cfg, traffic)):
            _, loss, down = fed.round()
            out["loss"].append(loss)
            if r < cfg["sync_interval"]:  # the span's sparse rounds
                out["down"].append(down)
            if r == 0:
                out["mu1"] = [dict(m) for m in fed.mu]
        out["params_end"] = fed.params
        out["hist0"], out["hist_end"] = fed.hist0, fed.hist
        t_train = time.perf_counter() - t
        out["eval"] = reference.evaluate(
            fed.params, splits, traffic["eval_split"], traffic["max_eval_triples"],
            cfg["method"], cfg["gamma"])
    say(phase="reference", rounds_s=t_train, eval_s=time.perf_counter() - t - t_train,
        train_s=fed.train_s)
    out["loss"], out["down"] = np.stack(out["loss"]), np.stack(out["down"])
    return out


# ------------------------------------------------------------------- eval
def run_eval(cfg, traffic, seed, seconds, trace, splits, t0, log):
    split = traffic["eval_split"]
    t = time.perf_counter()
    fed = federation(cfg, traffic, seed, splits)
    setup = {"clients_s": time.perf_counter() - t}
    t = time.perf_counter()
    blocks = [fed.evaluate(split)]
    setup["warm_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t0
    setup.update(log.summary())
    n_triples = sum(shapes(cfg, traffic, splits)["queries"])
    result = {"attempted": 0, "failed": 0}
    reduced = None
    if trace:
        calls = traffic["traced_calls"]
        got, tr = traced(lambda: [fed.evaluate(split) for _ in range(calls)])
        blocks += got
        result["attempted"] = calls
        reduced = tracereduce.reduce(tr, "window")
    else:
        w0 = time.perf_counter()
        marks = [w0]
        while True:
            blocks.append(fed.evaluate(split))
            marks.append(time.perf_counter())
            if marks[-1] - w0 >= seconds:
                break
        calls = len(marks) - 1
        result["attempted"] = calls
        result["eval_triples_per_s"] = n_triples * calls / (marks[-1] - w0)
        say(phase="calls", seconds=json.dumps(np.diff(marks).tolist()))
    result["failed"] = int(sum(not np.all(np.isfinite(b)) for b in blocks[1:]))
    result["memory_peak_bytes"] = memory_peak()
    say(phase="window", calls=calls, triples_per_call=n_triples)
    work = {"calls": calls}
    del fed
    gc.collect()
    t = time.perf_counter()
    ref_block = reference_eval(cfg, splits, seed, traffic, split)
    ref_s = time.perf_counter() - t
    numbers = check.eval_numbers(blocks, ref_block)
    return setup_s, setup, result, reduced, numbers, work, ref_s


def reference_eval(cfg, splits, seed, traffic, split, dtype=jnp.float32):
    """The reference's metric block of the seed's initial tables."""
    with jax.default_matmul_precision("highest"):
        params = [reference.init_tables(seed, c, s.num_entities,
                                        cfg["dataset"]["num_relations"],
                                        cfg["dim"], cfg["gamma"], dtype,
                                        cfg["method"])
                  for c, s in enumerate(splits)]
        return reference.evaluate(params, splits, split,
                                  traffic["max_eval_triples"], cfg["method"],
                                  cfg["gamma"])


KINDS = {"train": run_train, "eval": run_eval}


# ----------------------------------------------------------------- report
def execute(cfg, traffic, limits, end_to_end, per_layer, seed, seconds, trace,
            t0) -> dict:
    """Run the cell; returns the result object of the last line."""
    log = CompileLog()
    t = time.perf_counter()
    splits = kgdata.federation(cfg["dataset"], cfg["num_clients"])
    data_s = time.perf_counter() - t
    setup_s, setup, result, reduced, numbers, work, ref_s = KINDS[traffic["kind"]](
        cfg, traffic, seed, seconds, trace, splits, t0, log)
    say(phase="setup", setup_s=setup_s, data_s=data_s, **setup)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    metrics = {}
    if trace:
        device["busy_s"], device["window_s"] = reduced.busy_s, reduced.window_s
        ctx = {"trace": reduced, "work": work, "cfg": cfg,
               "shapes": shapes(cfg, traffic, splits),
               "peak": costs.peaks(dev.device_kind)}
        for m in per_layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        say(phase="trace", **{k: v["value"] for k, v in metrics.items()})
    else:
        values = dict(result, setup_s=setup_s)
        for m in end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    correct, compared = check.judge(numbers, limits)
    say(phase="check", reference_s=ref_s, correct=correct,
        not_compared=json.dumps({k: v for k, v in numbers.items() if k not in limits}))
    for name, c in compared.items():
        say(sys.stderr, check=name, value=c["value"], limit=c["limit"])
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": reduced.device_ops,
                            "idle_gaps": reduced.idle_gaps}
    out["check"] = compared
    return out


def main(argv=None, t0=None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    w, cfg, traffic, limits, end_to_end, per_layer = cell(CHECKOUT, args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU (first device is {devs[0].platform}); the "
              "benchmark runs on nothing else", file=sys.stderr)
        return 1
    if len(devs) < w["chips"]:
        print(f"bench: {w['name']} needs {w['chips']} chips, found {len(devs)}",
              file=sys.stderr)
        return 1
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    # keep every program, small ones too, so that only a checkout's first
    # run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = execute(cfg, traffic, limits, end_to_end, per_layer, args.seed,
                  args.seconds, bool(args.trace), t0)
    print(json.dumps(out), flush=True)
    return 0
