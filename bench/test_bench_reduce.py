"""The benchmark's trace reduction, cost arithmetic, peak table and its
refusal to run anywhere but on a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import costs
import tracereduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def hand_trace():
    """One TPU plane; ns times.  Window 0..1000.  Ops: a change_score
    kernel 100..300, a fusion 250..400 (overlaps it), a sparse_apply
    kernel 600..700, and an op 900..1100 that runs past the window."""
    ops = [
        ("change_score_pallas.1", 100, 300, "f32[171,1,256]"),
        ("fusion.7", 250, 400, "f32[8]"),
        ("sparse_apply_pallas.1", 600, 700, "f32[43776,256]"),
        ("fusion.9", 900, 1100, "f32[8]"),
    ]
    host = [
        ("window", 0, 1000, "window"),
        ("dispatch", 0, 120, "dispatch"),
        ("ledger_readback", 380, 650, "ledger_readback"),
        ("eval_readback", 700, 1000, "eval_readback"),
    ]
    return tr.Trace(devices={"/device:TPU:0": ops}, host=host)


def test_busy_idle_and_kernel_time():
    red = tr.reduce(hand_trace(), "window")
    # union: 100..400 (300) + 600..700 (100) + 900..1000 (100) = 500 ns
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(500e-9)
    assert red.op_s("change_score_pallas") == pytest.approx(200e-9)
    assert red.op_s("sparse_apply_pallas") == pytest.approx(100e-9)
    assert red.op_s("dist_cand_score_pallas") == 0.0
    assert [red.op_calls(k) for k in ("change_score_pallas", "sparse_apply_pallas",
                                      "dist_cand_score_pallas")] == [1, 1, 0]
    # an op that runs past the window counts only inside it
    assert red.op_s("fusion.9") == pytest.approx(100e-9)
    # idle: 0..100, 400..600, 700..900; longest first, named by host span
    assert [g[0] for g in red.idle_gaps] == ["ledger_readback", "eval_readback",
                                             "dispatch"]
    assert [g[1] for g in red.idle_gaps] == pytest.approx([200e-9, 200e-9, 100e-9])
    # ops clipped to the window: fusion.9 counts 100 ns of its 200
    assert dict(red.device_ops)["fusion.9 f32[8]"] == pytest.approx(100e-9)
    assert red.device_ops[0] == ["change_score_pallas.1 f32[171,1,256]",
                                 pytest.approx(200e-9)]


def test_nested_ops_count_their_own_time():
    # a loop 0..100 holding two ops 10..30 and 40..90, the second holding
    # one 50..60: own times 30, 20, 40, 10
    ops = [("while.1", 0, 100, ""), ("fusion.1", 10, 30, ""),
           ("fusion.2", 40, 90, ""), ("sort.3", 50, 60, "")]
    got = {n: t for n, _, t in tr.self_ns(ops)}
    assert got == {"while.1": 30, "fusion.1": 20, "fusion.2": 40, "sort.3": 10}
    assert tr.busy_ns(ops, 0, 100) == 100


def metric(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_idle_share_reader():
    mod = metric("idle_share.train")
    red = tr.reduce(hand_trace(), "window")
    assert mod.read({"trace": red}) == pytest.approx(50.0)


def test_reduce_needs_a_device_plane():
    t = hand_trace()
    t.devices = {}
    with pytest.raises(ValueError):
        tr.reduce(t, "window")


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("dispatch"):
            f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path), host_names={"window", "dispatch"})
    lo, hi = tr.span_of(t, "window")
    d_lo, d_hi = tr.span_of(t, "dispatch")
    assert lo <= d_lo < d_hi <= hi
    assert not t.devices  # no TPU plane on this host: nothing to reduce


def test_costs_by_hand():
    # TransE at D=256: 4 * 256 + 1 = 1025 FLOP per score; 10 triples with
    # 2 negatives a side score 10 * 5 = 50 times, forward + backward x3
    assert costs.train_flops("transe", 256, 10, 2) == 3 * 50 * 1025
    assert costs.train_flops("complex", 256, 1, 64) == 3 * 129 * 1536
    assert costs.eval_flops("transe", 256, 7) == 2 * 7 * 769
    assert costs.change_score_cost(100, 256) == (100 * 1540, 4 * (2 * 100 * 256 + 100))
    assert costs.sparse_apply_cost(10, 256) == (10 * 513, 4 * (3 * 10 * 256 + 10) + 10)
    f, b = costs.cand_score_cost("transe", 8, 128, 256)
    assert (f, b) == (8 * 128 * 769, 4 * (8 * 256 + 128 * 256 + 8 * 128))


def test_roofline_arithmetic():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # 200 FLOP take 2 s at peak, 50 bytes 5 s: memory-bound floor 5 s
    assert costs.roofline_pct(200, 50, 10.0, peak) == pytest.approx(50.0)
    # 2000 FLOP take 20 s: the compute floor bounds it
    assert costs.roofline_pct(2000, 50, 40.0, peak) == pytest.approx(50.0)
    assert costs.roofline_pct(200, 50, 0.0, peak) is None


def test_roofline_readers_count_the_real_work():
    """The kernels' readers count each client's real rows, never padding:
    two clients, 3 and 5 real queries against 10 and 20 entities; 7 and 9
    shared rows; D=256; the hand trace's kernel times."""
    red = tr.reduce(hand_trace(), "window")
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = {"trace": red, "peak": peak, "cfg": {"method": "transe", "dim": 256},
           "shapes": {"queries": [3, 5], "entities": [10, 20], "shared": [7, 9]},
           "work": {"calls": 1, "sparse_rounds": 1}}
    # change_score: 16 rows read twice and 16 scores written, over 200 ns
    nbytes = 4 * (2 * 16 * 256 + 16)
    assert metric("change_score_roofline").read(ctx) == pytest.approx(
        100 * nbytes / 1e9 / 200e-9)
    # sparse_apply: 16 rows of table and aggregate read and table written,
    # priorities (f32) and signs (int8), over 100 ns
    nbytes = 4 * (3 * 16 * 256 + 16) + 16
    assert metric("sparse_apply_roofline").read(ctx) == pytest.approx(
        100 * nbytes / 1e9 / 100e-9)
    # no dist_cand_score op in the trace: no reading, not 0
    assert metric("dist_cand_roofline").read(ctx) is None
    red.ops[0].append(("dist_cand_score_pallas.3", 0, 1000, ""))
    nbytes = 2 * 4 * ((3 * 256 + 10 * 256 + 3 * 10) + (5 * 256 + 20 * 256 + 5 * 20))
    assert metric("dist_cand_roofline").read(ctx) == pytest.approx(
        100 * nbytes / 1e9 / 1000e-9)


def test_peaks_by_device_kind():
    p = costs.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with open(os.path.join(HERE, "peaks.json")) as f:
        assert "TPU v5e" in json.load(f)["source"]
    with pytest.raises(KeyError):
        costs.peaks("TPU v4")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "r3-transe.train",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_harness_refuses_a_cpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_harness_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
