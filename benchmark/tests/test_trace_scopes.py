"""benchmark/trace_scopes.py against lists worked by hand, its protobuf walk
against a file written here field by field, and the old reducer left alone.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_trace_scopes.py
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace_reduce, trace_scopes as ts  # noqa: E402
from benchmark.metrics.readers import scopes as readers  # noqa: E402

VG, MV, RMV, LS = ("pml.objective.value_and_grad", "pml.features.matvec",
                   "pml.features.rmatvec", "pml.lbfgs.line_search")

SOLVE = "jit(_solve)/"
LOOP = SOLVE + "while/body/" + LS + "/while"
FIRST, INNER = SOLVE + VG, LOOP + "/body/" + VG  # the two places VG is entered

# One job by hand, as the chip schedules it: an evaluation before the solver's
# loop (0..2.75), set-up under no scope, a reshape of the loop's scatter-add
# that the compiler hoisted out of both loops (it runs once), then the outer
# while of 7 s (3..10) whose line search evaluates twice. Inside each
# evaluation sit a copy of loop state, which carries the while's own op_name,
# and the line search's own dot, which depends on nothing in the evaluation;
# between the two evaluations sits nothing. The select at 9..9.5 was fused
# across the scope's edge: its root lies outside the evaluation, so that is
# where it counts.
OPS = [
    (f"{FIRST}/{MV}/gather:", "%fusion.1", 0.0, 2.0),
    (f"{FIRST}/{RMV}/scatter-add:", "%fusion.3", 2.0, 0.75),
    (SOLVE + "jit(norm)/reduce_sum:", "%fusion.12", 2.75, 0.125),
    (f"{INNER}/{RMV}/reshape:", "%reshape.27", 2.875, 0.125),
    ("", "%while.136", 3.0, 7.0),
    (f"{INNER}/{MV}/gather:", "%fusion.21", 3.0, 2.0),
    (LOOP + ":", "%copy.9", 5.0, 0.125),
    (LOOP + "/body/dot_general:", "%fusion.27", 5.125, 0.125),
    (f"{INNER}/{RMV}/scatter-add:", "%fusion.22", 5.25, 0.75),
    (f"{INNER}/{MV}/gather:", "%fusion.21", 6.0, 2.0),
    (LOOP + ":", "%copy.9", 8.0, 0.125),
    (LOOP + "/body/dot_general:", "%fusion.27", 8.125, 0.125),
    (f"{INNER}/{RMV}/scatter-add:", "%fusion.22", 8.25, 0.75),
    (LOOP + "/body/select_n:", "%fusion.30", 9.0, 0.5),
    (SOLVE + "while/body/reduce_and:", "%fusion.40", 9.5, 0.5),
]


def scoped(scope, start, dur):
    return (f"jit(f)/{scope}/mul:", "%mul.1", start, dur)


def test_scope_path():
    assert ts.scope_path(
        "jit(cd_update_per_user)/vmap(pml.re.lane_solve)/while/body/"
        "pml.lbfgs.line_search/while/body/pml.objective.value_and_grad/"
        "pml.features.matvec/gather:") == (
            "pml.re.lane_solve", LS, VG, MV)
    assert ts.scope_path("jit(f)/transpose(jvp(pml.a.b))/mul") == ("pml.a.b",)
    assert ts.scope_path("jit(f)/while/body/add") == ()
    assert ts.scope_path("") == ()


def test_seconds_per_scope():
    got = ts.scope_seconds(OPS)
    # matvec 2 + 2 + 2, rmatvec 0.75 + 0.125 + 0.75 + 0.75; the evaluation
    # has no operation of its own, so its self time is 0 and its total the
    # kernels'. The line search's own: two copies, two dots, the select
    assert got[MV] == {"self_s": 6.0, "total_s": 6.0}
    assert got[RMV] == {"self_s": 2.375, "total_s": 2.375}
    assert got[VG] == {"self_s": 0.0, "total_s": 8.375}
    assert got[LS] == {"self_s": 1.0, "total_s": 1.0 + 0.125 + 2 * 2.75}
    # the while's own time is 7 less its body (7): 0; the set-up's 0.125 and
    # the last operation's 0.5
    assert got[ts.NO_SCOPE] == {"self_s": 0.625, "total_s": 0.625}
    assert sum(v["self_s"] for v in got.values()) == 10.0  # all busy time


def test_executions_per_scope():
    runs = ts.scope_runs(OPS)
    # 1 before the loop + 2 in the line search: %fusion.21 and %fusion.22 ran
    # twice there, the hoisted %reshape.27 once, and the largest count holds
    assert runs == {VG: 3, MV: 3, RMV: 3}
    # the line search is a scope around one while_loop: every instruction in
    # it is the while's own or in the loop, so it has no count
    assert LS not in runs
    # runs of consecutive operations would split each evaluation at the copy
    # and the dot and join the two across the loop's back edge, which is why
    # executions are not counted that way


def test_gap_attribution():
    # device busy 0..4 and 6..8 in a window of 0..12: gaps 4..6 and 8..12
    ops = [scoped(VG, 0.0, 4.0), scoped(VG, 6.0, 2.0)]
    spans = [("pml.cd.iteration", 0.0, 10.0), ("pml.cd.update", 0.5, 4.5),
             ("pml.cd.score", 5.0, 1.0), ("pml.cd.drain", 7.0, 3.0)]
    gaps = ts.idle_gaps(ops, (0.0, 12.0))
    assert gaps == [(4.0, 6.0), (8.0, 12.0)]
    assert ts.innermost_segments(spans) == [
        (0.0, 0.5, "pml.cd.iteration"), (0.5, 5.0, "pml.cd.update"),
        (5.0, 6.0, "pml.cd.score"), (6.0, 7.0, "pml.cd.iteration"),
        (7.0, 10.0, "pml.cd.drain")]
    by_span, winners = ts.gaps_by_span(gaps, spans)
    # 4..6: update 4..5, score 5..6 (a tie: the later name in the alphabet
    # is as good as any, what matters is the seconds). 8..12: drain 8..10,
    # nothing 10..12: half under one span and half under none, the span wins
    assert by_span == {"pml.cd.update": 1.0, "pml.cd.score": 1.0,
                       "pml.cd.drain": 2.0, ts.NO_SPAN: 2.0}
    assert winners[1] == "pml.cd.drain"
    assert winners[0] in ("pml.cd.update", "pml.cd.score")


def test_reduce_uses_only_the_dispatching_thread():
    ops = [scoped(VG, 1.0, 1.0), scoped(VG, 4.0, 1.0)]
    main, prefetch = ("/host:CPU", 0), ("/host:CPU", 1)
    threads = {
        main: [("pml.cd.update", 0.5, 1.0), ("pml.cd.objective", 2.5, 1.0)],
        # the prefetch thread covers every gap and more seconds; never used
        prefetch: [("pml.stream.prefetch", 0.0, 6.0)],
    }
    out = ts.reduce_scopes(ops, threads, (0.0, 6.0), window_thread=main)
    gaps = out["host_gaps"]
    assert gaps["idle_s"] == 4.0  # 0..1, 2..4, 5..6
    assert gaps["by_span"] == {
        "pml.cd.update": 0.5, "pml.cd.objective": 1.0, ts.NO_SPAN: 2.5}
    assert gaps["unattributed_s"] == 2.5
    assert gaps["longest"][0] == [2.0, 2.0, "pml.cd.objective"]
    assert out["scope_runs"] == {VG: 2}
    # with no window span in the trace, the thread that covers most wins
    assert ts.dispatching_thread(threads) == prefetch
    assert ts.reduce_scopes([], {}, None) == {
        "scopes": {}, "scope_runs": {}, "host_gaps": None}


def test_readers():
    reduced = ts.reduce_scopes(OPS, {}, (0.0, 10.0))
    ctx = {"trace": reduced, "jobs": 1}
    spec = {"scope": VG}
    assert readers.executions_per_job(dict(ctx, metric=spec)) == 3.0
    assert readers.seconds_per_execution(
        dict(ctx, metric={"scope": MV, "per": VG})) == 2.0
    assert readers.seconds_per_execution(
        dict(ctx, metric={"scope": RMV, "per": VG})) == 2.375 / 3
    assert readers.idle_unattributed_share(dict(ctx, metric={})) is None  # never idle
    idle = ts.reduce_scopes(OPS, {("h", 0): [("pml.glm.grid", 10.0, 1.0)]},
                            (0.0, 12.0), ("h", 0))
    assert readers.idle_unattributed_share(
        {"trace": idle, "jobs": 1, "metric": {}}) == 50.0
    # a reduced trace without the keys (today's harness), or a program
    # without the names (the parent): nothing, and no error
    old = trace_reduce.reduce_events(
        [("op", 0.0, 1.0)], [("jit__solve", 0.0, 1.0)], (0.0, 1.0))
    for reader, metric in ((readers.executions_per_job, spec),
                           (readers.seconds_per_execution, {"scope": MV, "per": VG}),
                           (readers.idle_unattributed_share, {})):
        assert reader({"trace": old, "jobs": 1, "metric": metric}) is None
        assert reader({"trace": None, "jobs": 1, "metric": metric}) is None
    unnamed = ts.reduce_scopes([("jit(f)/mul:", "%mul.1", 0.0, 1.0)], {}, (0.0, 1.0))
    assert readers.executions_per_job(
        {"trace": unnamed, "jobs": 1, "metric": spec}) is None


# -- the protobuf walk: an .xplane.pb written here, field by field -----------


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def _xspace():
    stat_meta = _field(5, _entry(7, _field(1, 7) + _field(2, "tf_op"))) \
        + _field(5, _entry(8, _field(1, 8) + _field(2, "flops")))
    gather = _field(1, 1) + _field(2, "%fusion.21 = f32[8]{0:T(8)} fusion(f32[4]{0} %p.1), kind=kCustom, calls=%fused.3") \
        + _field(5, _field(1, 8) + _field(3, 99)) \
        + _field(5, _field(1, 7) + _field(5, f"jit(_solve)/{VG}/{MV}/gather:"))
    copy = _field(1, 2) + _field(2, "%copy-start.1 = ...")
    scatter = _field(1, 3) + _field(2, "%fusion.22 = f32[4]{0} fusion(f32[8]{0} %p.2), kind=kLoop") \
        + _field(5, _field(1, 7) + _field(5, f"jit(_solve)/{VG}/{RMV}/scatter-add:"))
    ops_line = _field(2, "XLA Ops") + _field(3, 1000) \
        + _field(4, _field(1, 1) + _field(2, 2_000_000) + _field(3, 3_000_000)) \
        + _field(4, _field(1, 2) + _field(2, 5_000_000) + _field(3, 1_000_000)) \
        + _field(4, _field(1, 3) + _field(2, 6_000_000) + _field(3, 500_000))
    # the job's program at 3 us and, 0.1 us before the host's window span
    # opens at 1 us, one of the fillers jax launches at a job's start: 400 ps
    solve = _field(1, 4) + _field(2, "jit__solve(123)")
    filler = _field(1, 5) + _field(2, "jit_convert_element_type(7)")
    modules_line = _field(2, "XLA Modules") + _field(3, 0) \
        + _field(4, _field(1, 5) + _field(2, 900_000) + _field(3, 400)) \
        + _field(4, _field(1, 4) + _field(2, 3_000_000) + _field(3, 4_500_000))
    device = _field(2, "/device:TPU:0") + _field(3, modules_line) \
        + _field(3, ops_line) + _field(4, _entry(1, gather)) \
        + _field(4, _entry(2, copy)) + _field(4, _entry(3, scatter)) \
        + _field(4, _entry(4, solve)) + _field(4, _entry(5, filler)) + stat_meta
    window = _field(1, 1) + _field(2, trace_reduce.WINDOW_SPAN)
    span = _field(1, 2) + _field(2, "pml.glm.grid")
    thread = _field(2, "python3") + _field(3, 1000) \
        + _field(4, _field(1, 1) + _field(2, 0) + _field(3, 8_000_000)) \
        + _field(4, _field(1, 2) + _field(2, 500_000) + _field(3, 6_500_000))
    host = _field(2, "/host:CPU") + _field(3, thread) \
        + _field(4, _entry(1, window)) + _field(4, _entry(2, span))
    return _field(1, device) + _field(1, host)


@pytest.fixture
def trace_dir(tmp_path):
    run = tmp_path / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(_xspace())
    return tmp_path


def test_read_xspace_and_read_scopes(trace_dir):
    (path,) = (trace_dir / "plugins" / "profile" / "2026_01_01").iterdir()
    assert ts.newest_xplane(str(trace_dir)) == str(path)
    planes = ts.read_xspace(str(path))
    assert [p["name"] for p in planes] == ["/device:TPU:0", "/host:CPU"]
    ops = planes[0]["lines"][1]
    name, op_name, start, dur, module, run_id = ops["events"][0]
    assert (module, run_id) == ("", None)  # a TPU's operations carry neither
    assert name.startswith("%fusion.21") and op_name.endswith("/gather:")
    assert start == pytest.approx(1e-6 + 2e-6) and dur == pytest.approx(3e-6)
    assert ops["events"][1][1] == ""  # compiler-made
    (reduced,) = ts.read_scopes(str(trace_dir))
    # window 1..9 us; the gather 3..6 us, the copy 6..7 us, the scatter-add
    # 7..7.5 us; idle 1..3 and 7.5..9, the span covers 1.5..8: 0.5 + 1 of the
    # 3.5 idle microseconds have no span
    assert reduced["scopes"][MV]["total_s"] == pytest.approx(3e-6)
    assert reduced["scopes"][RMV]["total_s"] == pytest.approx(0.5e-6)
    assert reduced["scope_runs"] == {VG: 1, MV: 1, RMV: 1}
    assert reduced["host_gaps"]["idle_s"] == pytest.approx(3.5e-6)
    assert reduced["host_gaps"]["unattributed_s"] == pytest.approx(1.5e-6)
    assert ts.main([str(trace_dir)]) == 0
    assert ts.main([str(trace_dir / "nothing-here")]) == 1


# -- one way from a trace directory to the readers ----------------------------


def _metric(name):
    import json

    with open(os.path.join(os.path.dirname(trace_reduce.__file__), "metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_read_xplane_hands_the_readers_the_trace(trace_dir):
    """What ``harness.traced_window`` gets before it deletes the directory:
    the old reducer's keys and the scopes' three, from one parse; the four
    scope metrics as shipped read numbers from it."""
    from benchmark.metrics.readers import device

    (reduced,) = trace_reduce.read_xplane(str(trace_dir))
    (scoped,) = ts.read_scopes(str(trace_dir))
    for key in ("scopes", "scope_runs", "host_gaps"):
        assert reduced[key] == scoped[key]
    assert reduced["busy_s"] == pytest.approx(4.5e-6)
    assert reduced["window_s"] == pytest.approx(8e-6)
    assert reduced["programs"]["jit__solve"]["launches"] == 1
    ctx = {"trace": reduced, "jobs": 1}

    def read(name):
        spec = _metric(name)
        module, func = spec["reader"].split(":")
        assert module == "scopes"
        return getattr(readers, func)(dict(ctx, metric=spec))

    assert read("fe_evaluations_per_job") == 1.0
    assert read("fe_matvec_s_per_eval") == pytest.approx(3e-6)
    assert read("fe_rmatvec_s_per_eval") == pytest.approx(0.5e-6)
    assert read("idle_unattributed_share") == pytest.approx(100 * 1.5 / 3.5)
    # the names of the breakdown: scope, instruction without its number,
    # result shape; the gap a span of the program covers says so
    assert [name for name, _ in reduced["device_ops"]] == [
        f"{MV} | fusion f32[8]", "copy-start = ...", f"{RMV} | fusion f32[4]"]
    assert reduced["idle_gaps"][0] == [
        "after window start before jit__solve | host pml.glm.grid",
        pytest.approx(2e-6)]
    assert reduced["idle_gaps"][1] == [
        "after jit__solve before window end", pytest.approx(1.5e-6)]
    # the filler lies before the window and in no number but the launch
    # count, which is of the whole trace
    assert list(reduced["programs"]) == ["jit__solve"]
    assert reduced["launches"] == 2
    assert device.launches_per_job(
        dict(ctx, metric=_metric("device_launches_per_job"))) == 2.0


def test_launch_count_is_of_the_whole_trace():
    """The cell's launches as the chip's traces held them (PERF.md section
    3): three fillers of microseconds and the solve, the window's edge on the
    device's clock before, between or after the fillers. One count."""
    from benchmark.metrics.readers import device

    spec = _metric("device_launches_per_job")
    launches = [("jit_convert_element_type", 0.9998, 6e-7),
                ("jit_broadcast_in_dim", 0.9999, 1.34e-5),
                ("jit_convert_element_type", 1.0002, 6e-7),
                ("jit__solve", 1.0013, 15.0)]
    for edge in (0.9990, 1.0000, 1.0005):
        reduced = trace_reduce.reduce_events([], launches, (edge, 16.1))
        assert device.launches_per_job(
            {"trace": reduced, "jobs": 1, "metric": spec}) == 4.0
        assert reduced["programs"]["jit__solve"]["launches"] == 1
    assert device.launches_per_job(
        {"trace": reduced, "jobs": 2, "metric": spec}) == 2.0
    empty = trace_reduce.reduce_events([], [], None)
    assert device.launches_per_job(
        {"trace": empty, "jobs": 1, "metric": spec}) is None


# -- the CPU backend's trace goes the same way ---------------------------------


def _host_xspace():
    """What the CPU backend writes (the rehearsal): no device plane; the
    host's operations carry ``hlo_module`` (a reference to a name) and
    ``run_id`` as stats of the event itself."""
    stat_meta = _field(5, _entry(3, _field(1, 3) + _field(2, "run_id"))) \
        + _field(5, _entry(10, _field(1, 10) + _field(2, "hlo_module"))) \
        + _field(5, _entry(11, _field(1, 11) + _field(2, "jit_f")))

    def op(meta, offset_ps, duration_ps, run):
        return _field(4, _field(1, meta) + _field(2, offset_ps)
                      + _field(3, duration_ps)
                      + _field(4, _field(1, 10) + _field(7, 11))
                      + _field(4, _field(1, 3) + _field(4, run)))

    python = _field(2, "python") + _field(3, 1000) \
        + _field(4, _field(1, 1) + _field(2, 0) + _field(3, 8_000_000)) \
        + _field(4, _field(1, 2) + _field(2, 500_000) + _field(3, 6_500_000))
    # two runs of jit_f on a worker thread: 2..4 us (copy, dot) and 6.5..7 us;
    # the marker between them carries no module and is no operation
    worker = _field(2, "tf_XLAPjRtCpuClient/1") + _field(3, 1000) \
        + op(3, 1_000_000, 500_000, 77) + op(4, 1_500_000, 1_500_000, 77) \
        + _field(4, _field(1, 5) + _field(2, 3_000_000) + _field(3, 100_000)) \
        + op(4, 5_500_000, 500_000, 78)
    host = _field(2, "/host:CPU") + _field(3, python) + _field(3, worker) \
        + _field(4, _entry(1, _field(1, 1) + _field(2, trace_reduce.WINDOW_SPAN))) \
        + _field(4, _entry(2, _field(1, 2) + _field(2, "pml.glm.grid"))) \
        + _field(4, _entry(3, _field(1, 3) + _field(2, "copy.6"))) \
        + _field(4, _entry(4, _field(1, 4) + _field(2, "dot.7"))) \
        + _field(4, _entry(5, _field(1, 5) + _field(2, "end: copy.6"))) \
        + stat_meta
    return _field(1, host)


def test_cpu_trace_goes_through_the_same_parser(tmp_path):
    run = tmp_path / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(_host_xspace())
    (reduced,) = trace_reduce.read_xplane(str(tmp_path))
    assert reduced["window_s"] == pytest.approx(8e-6)
    assert reduced["busy_s"] == pytest.approx(2.5e-6)
    assert reduced["launches"] == 2  # two run_ids of one module
    assert reduced["programs"]["jit_f"]["seconds"] == pytest.approx(2.5e-6)
    assert dict(map(tuple, reduced["device_ops"])) == {
        "dot": pytest.approx(2e-6), "copy": pytest.approx(0.5e-6)}
    assert reduced["idle_gaps"][0] == [
        "after jit_f before jit_f | host pml.glm.grid", pytest.approx(2.5e-6)]
    # no operation of the CPU backend has a scope: the scope readers get
    # nothing to read
    assert list(reduced["scopes"]) == [ts.NO_SCOPE] and not reduced["scope_runs"]
    ctx = {"trace": reduced, "jobs": 1}
    assert readers.executions_per_job(dict(ctx, metric={"scope": VG})) is None
    assert readers.seconds_per_execution(
        dict(ctx, metric={"scope": MV, "per": VG})) is None


def test_device_ops_keys_hold_no_fusion_number():
    key = trace_reduce.op_key
    path = f"jit(_solve)/while/body/{LS}/while/body/{VG}/pml.objective.row_block/"
    gather = ("%fusion.23 = f32[4194304]{0:T(1024)} fusion(f32[2097152]{0:T(1024)S(1)} "
              "%multiply.7, s32[4194304]{0} %bitcast.5), kind=kCustom, "
              "calls=%fused_computation.19")
    assert key(path + MV + "/gather:", gather) == f"{MV} | fusion f32[4194304]"
    assert key(path + MV + "/gather:", gather.replace("fusion.23", "fusion.19")) \
        == f"{MV} | fusion f32[4194304]"
    pair = ("%compare_select_fusion.16 = (s32[65536,64]{1,0}, s32[65536,64]{1,0}) "
            "fusion(s32[4194304]{0} %p), kind=kLoop, calls=%fused.2")
    assert key(path + "select_n:", pair) == \
        "pml.objective.row_block | compare_select_fusion (s32[65536,64], s32[65536,64])"
    # under no scope: today's label, less the number
    loop = "%while.148 = (s32[], f32[2097152]{0}) while((s32[], f32[2097152]{0}) %tuple.3), condition=%c, body=%b"
    assert key("", loop) == \
        "while = (s32[], f32[2097152]) while((s32[], f32[2097152])), condition=%c, body=%b"
    assert key("jit(_solve)/jit(norm)/reduce_sum:", "%fusion.12") == "fusion"
    long = "%fusion.5 = (" + ", ".join(["f32[2097152]{0}"] * 40) + ") fusion()"
    assert len(key(path + "x:", long)) == 160 and len(key("", long)) == 160
    # operations of one key are summed, whatever their numbers: the hand-made
    # job's three gathers (%fusion.1 before the loop, %fusion.21 twice in it)
    reduced = trace_reduce.reduce_events(
        [(key(o, i), s, d) for o, i, s, d in OPS], [], (0.0, 10.0))
    top = dict(map(tuple, reduced["device_ops"]))
    assert top[f"{MV} | fusion"] == 6.0
    assert top[f"{RMV} | fusion"] == 2.25 and top[f"{RMV} | reshape"] == 0.125
    import re
    assert not any(re.search(r"fusion\.\d", name) for name in top)


# -- the old reducer is left alone --------------------------------------------


def test_old_reducer_returns_what_it_returned():
    """The stored list of benchmark/check.py through the parent's reducer:
    every key and number it gave at PR 24, whatever is added beside it."""
    ops = [("loop", 0.0, 4.0), ("gather", 0.5, 1.0), ("scatter", 1.5, 2.0),
           ("dot", 6.0, 2.0), ("add", 8.0, 1.0)]
    launches = [("a", 0.0, 4.0), ("b", 6.0, 2.0), ("a", 8.0, 1.0)]
    out = trace_reduce.reduce_events(ops, launches, (0.0, 10.0))
    assert out == {
        "busy_s": 7.0, "window_s": 10.0, "launches": 3,
        "programs": {"a": {"seconds": 5.0, "launches": 2, "order": 0},
                     "b": {"seconds": 2.0, "launches": 1, "order": 1}},
        "device_ops": [["scatter", 2.0], ["dot", 2.0], ["gather", 1.0],
                       ["loop", 1.0], ["add", 1.0]],
        "idle_gaps": [["after a before b", 2.0],
                      ["after a before window end", 1.0]],
    }
    # the same events, scoped, give the same busy and idle seconds
    new = ts.reduce_scopes(
        [(f"jit(a)/pml.{n}/x:", n, s, d) for n, s, d in ops], {}, (0.0, 10.0))
    assert new["host_gaps"]["idle_s"] == out["window_s"] - out["busy_s"]
    assert sum(v["self_s"] for v in new["scopes"].values()) == out["busy_s"]
