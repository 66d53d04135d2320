"""Device time by named scope and the program's span counters, on a trace
built by hand; and the readers of both."""
import importlib.util
import os

import jax
import pytest

import harness
import scopes
import tracereduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
BODY = "jit(fused_sparse)/feds/train/while/body/closed_call/"


def hand_trace():
    """One TPU plane; ns times; window 0..1000.  A training loop 0..600
    holds a gather (100 ns), a backward op under ``transpose(jvp(...))``
    (200), a scatter (150) and an op the compiler made with no op_name,
    which the profiler names by the loop (50), so the loop's own time is
    100.  Then an
    upload fusion of the sparse program and a fusion of the same name in
    the sync program, an op with no op_name, and an eval score op that
    runs past the window's end."""
    m_sp, m_sy, m_ev = "jit_fused_sparse", "jit_fused_sync", "jit_eval_core"
    ops = [
        ((m_sp, "while.3"), 0, 600, "jit(fused_sparse)/feds/train/while"),
        ((m_sp, "fusion.1"), 10, 110,
         BODY + "feds/train/feds/train/gather/gather"),
        ((m_sp, "fusion.2"), 120, 320,
         BODY + "feds/train/transpose(jvp(feds/train/score_grad))/mul"),
        ((m_sp, "fusion.3"), 330, 480,
         BODY + "feds/train/feds/train/scatter/scatter-add"),
        # the compiler's rewrite of a scatter, named by the loop
        ((m_sp, "sort.5"), 480, 530, "jit(fused_sparse)/feds/train/while"),
        ((m_sp, "fusion.7"), 600, 650,
         "jit(fused_sparse)/feds/comm/feds/comm/upload/top_k"),
        ((m_sy, "fusion.7"), 700, 730,
         "jit(fused_sync)/feds/comm/feds/comm/sync/div"),
        ((m_sp, "copy.4"), 730, 740, ""),
        ((m_ev, "fusion.9"), 900, 1100,
         "jit(eval_core)/feds/eval/feds/eval/score/sub"),
    ]
    steps = {"client_steps": 807, "client_steps_real": 483}
    host = [
        ("window", 0, 1000, {}),
        ("dispatch", 0, 5, {}),
        ("telemetry/round", 0, 650, dict(t=0, kind="sparse", **steps)),
        ("telemetry/round", 650, 740, dict(t=1, kind="sync", **steps)),
        ("telemetry/ledger_flush", 740, 800, {"rounds": 2}),
        ("telemetry/eval", 880, 1000,
         {"split": "test", "scores": 10, "scores_real": 6}),
        # after the window: left out
        ("telemetry/round", 1200, 1300, dict(t=2, kind="sparse", **steps)),
    ]
    return tr.Trace(devices={"/device:TPU:0": ops}, host=host)


def test_own_time_by_innermost_scope():
    s = scopes.reduce(hand_trace())
    want = {"feds/train": 150, "feds/train/gather": 100,
            "feds/train/score_grad": 200, "feds/train/scatter": 150,
            "feds/comm/upload": 50, "feds/comm/sync": 30,
            "feds/eval/score": 100}
    assert s.scope_s == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert s.unscoped_s == pytest.approx(10e-9)
    assert s.own_s("feds/train/adam") is None
    assert s.under_s("feds/comm") == pytest.approx(80e-9)
    assert s.under_s("feds/train") == pytest.approx(600e-9)
    assert s.under_s("feds/eval/rank") is None
    # the sort, not the loop op that names it
    assert s.loop_unnamed_s("feds/train") == pytest.approx(50e-9)
    assert s.loop_unnamed_s("feds/comm") == 0
    # busy: 0..650, 700..740 and 900..1000
    assert s.busy_s == pytest.approx(790e-9)
    assert s.window_s == pytest.approx(1000e-9)


def test_ops_are_keyed_by_module_and_name():
    ops = scopes.reduce(hand_trace()).ops
    assert ops[("jit_fused_sparse", "fusion.7")][0] == "feds/comm/upload"
    assert ops[("jit_fused_sync", "fusion.7")][0] == "feds/comm/sync"
    assert ops[("jit_fused_sparse", "copy.4")][0] is None


def test_span_counters_of_the_window():
    s = scopes.reduce(hand_trace())
    assert s.counter("round", "client_steps") == 2 * 807
    assert s.counter("round", "client_steps_real") == 2 * 483
    assert s.counter("eval", "scores_real") == 6
    assert s.counter("ledger_flush", "rounds") == 2
    assert s.counter("round", "scores") is None
    assert s.counter("stage", "rounds") is None


def test_innermost_matches_whole_path_components():
    pat = scopes._scope_pattern(scopes.SCOPES)
    inner = lambda n: scopes.innermost(n, pat)  # noqa: E731
    assert inner("jit(f)/feds/train/while/body/x") == "feds/train"
    assert inner("jit(f)/feds/train/transpose(jvp(feds/train/adam))/mul") == (
        "feds/train/adam")
    assert inner("jit(f)/feds/trainer/x") is None
    assert inner("jit(f)/xfeds/comm/x") is None
    assert inner("jit(f)/feds/comm/feds/comm/sync") == "feds/comm/sync"
    assert inner("") is None
    assert scopes.innermost("jit(f)/feds/train/x", None) is None


def metric(name):
    spec = importlib.util.spec_from_file_location(
        "scopes_metric_" + name, os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


TRAIN = ("train_gather_s", "train_score_grad_s", "train_scatter_s",
         "train_adam_s", "feds_comm_s", "train_useful_share")
EVAL = ("eval_score_s", "eval_filter_s", "eval_rank_s", "eval_useful_share")


def test_readers(monkeypatch):
    s = scopes.reduce(hand_trace())
    monkeypatch.setattr(scopes, "read", lambda: s)
    ctx = {"work": {"rounds": 2, "calls": 1}}
    got = {n: metric(n)(ctx) for n in TRAIN + EVAL}
    assert got == pytest.approx({
        "train_gather_s": 50e-9, "train_score_grad_s": 100e-9,
        "train_scatter_s": 100e-9, "train_adam_s": None,
        "feds_comm_s": 40e-9, "train_useful_share": 100 * 483 / 807,
        "eval_score_s": 100e-9, "eval_filter_s": None, "eval_rank_s": None,
        "eval_useful_share": 60.0,
    })


def test_readers_find_nothing_in_a_program_without_scopes(monkeypatch):
    t = hand_trace()
    t.host = [h for h in t.host if not h[0].startswith("telemetry/")]
    s = scopes.reduce(t, names=())
    assert s.scope_s == {} and s.spans == []
    monkeypatch.setattr(scopes, "read", lambda: s)
    ctx = {"work": {"rounds": 2, "calls": 1}}
    assert [metric(n)(ctx) for n in TRAIN + EVAL] == [None] * 10


def test_read_finds_no_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    assert scopes.read() is None
    assert scopes.seconds_per("feds/train", 1) is None
    assert scopes.share("round", "client_steps_real", "client_steps") is None


def test_load_trace_keeps_span_attributes(tmp_path, monkeypatch):
    f = jax.jit(lambda x: x * 2)
    f(jax.numpy.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("telemetry/round", t=3,
                                          kind="sparse", client_steps=9):
            f(jax.numpy.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    path = [os.path.join(d, n) for d, _, ns in os.walk(tmp_path) for n in ns
            if n.endswith(".xplane.pb")][0]
    t = scopes.load_trace(path)
    spans = [h for h in t.host if h[0] == "telemetry/round"]
    assert [h[3] for h in spans] == [{"t": 3, "kind": "sparse",
                                      "client_steps": 9}]
    assert not t.devices  # no TPU plane on this host
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    assert scopes.read() is None  # nothing to reduce without a device


# ------------------------------------------------- a raw .xplane.pb by hand
def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _msg(*fields):
    """Protobuf bytes of ``(field number, int | str | bytes)`` pairs."""
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


def _plane(name, stat_names, events, lines):
    """An XPlane: ``events`` {id: (name, [(stat id, value)])}, ``lines``
    [(name, timestamp_ns, [(event id, offset_ps, duration_ps, stats)])]."""
    def stat(sid, v):
        return _msg((1, sid), (5 if isinstance(v, str) else 3, v))

    fields = [(2, name)]
    for sid, sname in stat_names.items():
        fields.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    for eid, (ename, stats) in events.items():
        md = _msg((1, eid), (2, ename), *[(5, stat(*x)) for x in stats])
        fields.append((4, _msg((1, eid), (2, md))))
    for lname, ts, evs in lines:
        body = [(2, lname), (3, ts)]
        for eid, off, dur, stats in evs:
            body.append((4, _msg((1, eid), (2, off), (3, dur),
                                 *[(4, stat(*x)) for x in stats])))
        fields.append((3, _msg(*body)))
    return _msg(*fields)


def raw_trace():
    """One TPU plane running a sparse program (id 11: a loop 0..500 ns
    holding a backward op 100..300, then an upload fusion) and a sync
    program (id 22) whose fusion has the upload fusion's very name; host
    spans with attributes.  Times from 1,000 ns."""
    fus = "%fusion.7 = f32[8]{0} fusion(), kind=kLoop"
    tpu = _plane("/device:TPU:0", {1: "tf_op", 2: "program_id"}, {
        1: (fus, [(1, BODY[:17] + "feds/comm/feds/comm/upload/top_k:"),
                  (2, 11)]),
        2: (fus, [(1, "jit(fused_sync)/feds/comm/feds/comm/sync/div:"),
                  (2, 22)]),
        3: ("%while.3 = (f32[8]{0}) while()",
            [(1, "jit(fused_sparse)/feds/train/while:"), (2, 11)]),
        4: ("%fusion.2 = f32[8]{0} fusion()",
            [(1, BODY + "transpose(jvp(feds/train/score_grad))/mul:"),
             (2, 11)]),
        5: ("jit_fused_sparse(11)", []),
        6: ("jit_fused_sync(22)", []),
    }, [
        ("XLA Modules", 1000, [(5, 0, 600_000, []), (6, 700_000, 100_000, [])]),
        ("XLA Ops", 1000, [(3, 0, 500_000, []), (4, 100_000, 200_000, []),
                           (1, 520_000, 50_000, []), (2, 700_000, 30_000, [])]),
    ])
    host = _plane("/host:CPU", {1: "client_steps", 2: "kind"}, {
        1: ("window", []), 2: ("telemetry/round", []),
    }, [("python", 1000, [
        (1, 0, 1_000_000, []),
        (2, 0, 650_000, [(1, 807), (2, "sparse")]),
    ])])
    return _msg((1, tpu), (1, host))


def test_load_trace_reads_op_names_from_the_event_metadata(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(raw_trace())
    t = scopes.load_trace(str(path))
    ops = {(k, s, e): op for k, s, e, op in t.devices["/device:TPU:0"]}
    sp, sy = "jit_fused_sparse(11)", "jit_fused_sync(22)"
    assert ops == {
        ((sp, "while.3"), 1000, 1500): "jit(fused_sparse)/feds/train/while",
        ((sp, "fusion.2"), 1100, 1300):
            BODY + "transpose(jvp(feds/train/score_grad))/mul",
        ((sp, "fusion.7"), 1520, 1570):
            BODY[:17] + "feds/comm/feds/comm/upload/top_k",
        ((sy, "fusion.7"), 1700, 1730):
            "jit(fused_sync)/feds/comm/feds/comm/sync/div",
    }
    assert ("telemetry/round", 1000, 1650,
            {"client_steps": 807, "kind": "sparse"}) in t.host
    s = scopes.reduce(t)
    assert s.scope_s == pytest.approx({
        "feds/train": 300e-9, "feds/train/score_grad": 200e-9,
        "feds/comm/upload": 50e-9, "feds/comm/sync": 30e-9})
    assert s.ops[(sp, "fusion.7")][0] == "feds/comm/upload"
    assert s.ops[(sy, "fusion.7")][0] == "feds/comm/sync"
    assert s.counter("round", "client_steps") == 807
