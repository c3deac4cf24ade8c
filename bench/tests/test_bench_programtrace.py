"""What the program writes into a profiler trace: the stage of each
device operation, the program's host spans, and the metrics read from
them; on hand-made data and on traces recorded on a v5e."""
import gzip
from pathlib import Path

import numpy as np
import pytest

from bench import costs, harness, schedule
from bench import programtrace as pt
from bench import tracereduce as tr

FIXTURE = Path(__file__).resolve().parent / "fixtures"
CELL = harness.load_cell("cifar10-golddiff.batch")
PEAK = costs.peaks("TPU v5 lite")


def _program(intervals, stages=None, host=None, spans=None,
             window=(0.0, 100.0), names=None, shift=0.0):
    st = np.array([a for a, _ in intervals], float)
    en = np.array([b for _, b in intervals], float)
    names = names or [f"op{i}" for i in range(len(intervals))]
    dev = tr.DeviceOps("/device:TPU:0", st, en, list(names))
    trace = tr.Reduced(window=window, devices=[dev],
                       host=host or {k: [] for k in tr.HOST_SPANS})
    return pt.Program(trace, [list(stages or [""] * len(intervals))],
                      spans or {}, shift)


@pytest.mark.parametrize("path,stage", [
    ("jit(traced)/while/body/closed_call/stage.rerank/jit(support_sqdist)"
     "/gather:", "stage.rerank"),
    ("jit(traced)/while/body/closed_call/stage.screen/top_k:",
     "stage.screen"),
    ("jit(f)/stage.fused_step/stage.aggregate/add:", "stage.aggregate"),
    ("jit(traced)/while/body/closed_call/jit(take_along_axis)/gather:", ""),
    ("", ""),
    ("jit(f)/stage.rerank/sub", "stage.rerank"),
])
def test_stage_of_takes_the_innermost_stage(path, stage):
    assert pt.stage_of(path) == stage


def test_stage_time_counts_each_operation_once():
    # a loop (0-60, no stage) around a screen (0-10), a re-rank whose
    # kernel nests in a gather fusion (10-30 holding 15-25), an
    # unscoped op (30-35) and an aggregate (50-60)
    p = _program([(0, 60), (0, 10), (10, 30), (15, 25), (30, 35), (50, 60)],
                 stages=["", "stage.screen", "stage.rerank", "stage.rerank",
                         "", "stage.aggregate"])
    t = p.stage_times()
    assert t == pytest.approx({"stage.screen": 10e-9, "stage.rerank": 20e-9,
                               "stage.aggregate": 10e-9,
                               pt.UNSCOPED: 20e-9})
    assert p.stage_s("rerank") == pytest.approx(20e-9)
    assert p.stage_s("ivf_screen") == 0.0
    assert sum(t.values()) == pytest.approx(p.trace.busy_s())
    # a program without stage scopes: everything is unscoped
    assert _program([(0, 10), (20, 30)]).stage_times() == pytest.approx(
        {pt.UNSCOPED: 20e-9})


HOST = {"submit": [(60.0, 62.0)], "pump": [(0.0, 60.0), (62.0, 100.0)],
        "wait": []}
SPANS = {"runtime.pump": [(0.0, 60.0), (62.0, 100.0)],
         "wave.segment": [(1.0, 45.0), (63.0, 99.0)],
         "wave.execute": [(2.0, 40.0), (64.0, 97.0)],
         "wave.d2h": [(40.0, 44.0)],
         "runtime.post": [(45.0, 59.0)],
         "runtime.admit": [(62.0, 63.0)]}


def test_idle_gaps_carry_the_innermost_program_span():
    # busy 0-40 and 64-97: gaps 40-64 and 97-100
    p = _program([(0, 40), (64, 97)], host=HOST, spans=SPANS)
    gaps = p.idle_gaps()
    assert [g[1] for g in gaps] == pytest.approx([24e-9, 3e-9])
    # 40-64: runtime.pump (40-60) and runtime.post (45-59) each cover
    # half of it or more; runtime.post is the innermost
    assert gaps[0][0] == "pump/runtime.post"
    # 97-100: runtime.pump and wave.segment (97-99) cover half or more
    assert gaps[1][0] == "pump/wave.segment"
    # where no span covers half the gap, the one that covers the most
    part = _program([(0, 40), (64, 97)], host=HOST,
                    spans={"wave.d2h": [(41.0, 45.0)],
                           "runtime.post": [(46.0, 52.0)]})
    assert part.idle_gaps()[0][0] == "pump/runtime.post"
    # a gap no program span overlaps keeps the harness label
    lone = _program([(0, 40)], host=HOST, spans={"wave.d2h": [(1.0, 2.0)]})
    assert lone.idle_gaps() == [["pump", pytest.approx(60e-9)]]


def test_idle_by_span_splits_each_gap_by_the_innermost_span():
    p = _program([(0, 40), (64, 97)], host=HOST, spans=SPANS)
    by = dict(p.idle_by_span())
    # 40-64: d2h 40-44, segment 44-45, post 45-59, pump 59-60, the
    # harness alone 60-62, admit 62-63, segment 63-64 (before execute);
    # 97-100: segment 97-99, pump 99-100
    assert by == pytest.approx({
        "wave.d2h": 4e-9, "wave.segment": 1e-9 + 1e-9 + 2e-9,
        "runtime.post": 14e-9, "runtime.pump": 1e-9 + 1e-9,
        "-": 2e-9, "runtime.admit": 1e-9})
    assert sum(by.values()) == pytest.approx(27e-9)


def test_device_clock_shift_keeps_programs_after_their_dispatch():
    ex = [(10.0, 30.0), (40.0, 60.0)]
    # the second program seems to start 3 before its dispatch
    assert pt.device_shift(ex, [(11.0, 29.0), (37.0, 55.0), (5.0, 6.0)]) \
        == pytest.approx(3.0)
    assert pt.device_shift(ex, [(11.0, 29.0), (41.0, 59.0)]) == 0.0
    assert pt.device_shift([], [(1.0, 2.0)]) == 0.0
    # the shift moves the device gaps onto the host's clock
    p = _program([(0, 40), (64, 97)], host=HOST, spans=SPANS, shift=2.0)
    assert p.idle_gaps()[0][0] == "pump/runtime.post"
    assert dict(p.idle_by_span())["wave.d2h"] == pytest.approx(2e-9)


# -- the protobuf wire format, on a hand-made XSpace --------------------------
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A message from (field number, int | str | bytes | float)."""
    out = b""
    for num, val in fields:
        if isinstance(val, float):                 # fixed64 (a double)
            out += _varint(num << 3 | 1) + np.float64(val).tobytes()
        elif isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


def test_op_paths_read_tf_op_from_the_wire(tmp_path):
    rerank = "jit(f)/stage.rerank/jit(support_sqdist)/gather:"
    screen = "jit(f)/stage.screen/top_k:"
    stat_meta = [(5, _msg((1, k), (2, _msg((1, k), (2, nm)))))
                 for k, nm in ((26, "tf_op"), (3, "flops"), (9, screen))]
    ev_meta = [   # a str value, a ref to a stat name, no tf_op at all
        (4, _msg((1, 1), (2, _msg((1, 1), (2, "%gather.1 = ..."),
                                  (5, _msg((1, 3), (2, 1.5))),
                                  (5, _msg((1, 26), (5, rerank))))))),
        (4, _msg((1, 2), (2, _msg((1, 2), (5, _msg((1, 26), (7, 9))))))),
        (4, _msg((1, 3), (2, _msg((1, 3), (5, _msg((1, 3), (4, 7))))))),
    ]
    ops = _msg((1, 5), (2, "XLA Ops"), (3, 1000),
               *[(4, _msg((1, m), (2, 10 * i), (3, 5))) for i, m in
                 enumerate([1, 3, 2, 1])])
    modules = _msg((2, "XLA Modules"), (4, _msg((1, 2), (2, 0), (3, 50))))
    device = _msg((1, 7), (2, "/device:TPU:0"), (3, modules), (3, ops),
                  *ev_meta, *stat_meta)
    host = _msg((2, "/host:CPU"), (3, _msg((2, "main"),
                                           (4, _msg((1, 1), (3, 9))))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, host), (1, device)))
    assert pt.op_paths(path) == {"/device:TPU:0": [rerank, "", screen,
                                                   rerank]}


# -- the metric readers -------------------------------------------------------
def _run(prog, segments):
    return harness.Run(CELL, 20.0, 80.0, 20.0, [], segments,
                       None if prog is None else prog.trace, PEAK,
                       schedule.steps(CELL.config))


def test_stage_roofline_and_seam_read_the_program(monkeypatch):
    seg = [{"start": 0, "stop": 2, "active": 8, "bucket": 8, "dur": 0.1}]
    prog = _program([(0, 8e6), (10e6, 18e6), (20e6, 28e6), (30e6, 38e6)],
                    stages=["stage.screen", "stage.rerank", "stage.rerank",
                            "stage.aggregate"], window=(0.0, 1e9),
                    spans={"wave.execute": [(0.0, 40e6), (42e6, 80e6),
                                            (86e6, 99e6)]})
    monkeypatch.setattr(pt, "of_run", lambda run: prog)
    least = {s: 0.0 for s in ("screen", "rerank", "aggregate")}
    for st in schedule.steps(CELL.config)[:2]:
        for s, c in costs.stage_costs(CELL.config, st.m, st.k, 8).items():
            least[s] += costs.least_time(c["flops"], c["bytes"], PEAK)
    run = _run(prog, seg)
    assert pt.stage_roofline(run, "rerank") == pytest.approx(
        100.0 * least["rerank"] / 16e-3)
    assert pt.stage_roofline(run, "screen") == pytest.approx(
        100.0 * least["screen"] / 8e-3)
    assert pt.seam_ms(run) == pytest.approx((2.0 + 6.0) / 2)
    # a program without the scopes or the spans reads nothing
    bare = _program([(0, 8e6)], window=(0.0, 1e9))
    monkeypatch.setattr(pt, "of_run", lambda run: bare)
    assert pt.stage_roofline(_run(bare, seg), "rerank") is None
    assert pt.seam_ms(_run(bare, seg)) is None
    monkeypatch.setattr(pt, "of_run", lambda run: None)
    assert pt.stage_roofline(_run(None, seg), "rerank") is None
    assert pt.seam_ms(_run(None, seg)) is None


@pytest.mark.parametrize("metric", ["screen_stage_roofline",
                                    "rerank_stage_roofline",
                                    "aggregate_stage_roofline",
                                    "seam_ms.batch"])
def test_new_metrics_read_nothing_from_an_untraced_run(metric):
    assert harness.reader(metric)(_run(None, [])) is None


# -- traces recorded on a v5e -------------------------------------------------
def test_a_program_without_scopes_or_spans_reads_as_such():
    """The trace recorded before the program had stage scopes and
    mirrored spans: every operation unscoped, no program span."""
    prog = pt.reduce(FIXTURE / "v5e_cifar10_batch.xplane.pb")
    assert set(prog.stage_times()) == {pt.UNSCOPED}
    assert prog.spans == {} and prog.seam_ms() is None
    assert prog.shift == 0.0
    assert all(label in tr.HOST_SPANS for label, _ in prog.idle_gaps())


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    """Half a second of the batch cell, traced on one v5e with the
    engine's stage scopes and the runtime's spans in the trace."""
    path = tmp_path_factory.mktemp("trace") / "scoped.xplane.pb"
    path.write_bytes(gzip.decompress(
        (FIXTURE / "v5e_cifar10_batch_scoped.xplane.pb.gz").read_bytes()))
    return pt.reduce(path)


def test_scoped_trace_stages_cover_the_busy_time(scoped):
    t = scoped.stage_times()
    busy = scoped.trace.busy_s()
    assert sum(t.values()) == pytest.approx(busy)
    staged = [t.get(f"stage.{s}", 0.0)
              for s in ("screen", "rerank", "aggregate")]
    assert all(v > 0.0 for v in staged)
    assert sum(staged) >= 0.85 * busy


@pytest.mark.parametrize("kernel,stage", [
    ("support_sqdist", "stage.rerank"),
    ("golden_support_aggregate", "stage.aggregate"),
    ("pdist", "stage.screen"),
])
def test_scoped_trace_kernels_keep_their_names(scoped, kernel, stage):
    """The kernel rooflines still find their kernels by instruction
    name, each inside its own stage."""
    assert scoped.trace.kernel_s(kernel) > 0.0
    i, dev = next((i, d) for i, d in enumerate(scoped.trace.devices)
                  if d.start.size)
    assert {st for nm, st in zip(dev.names, scoped.stages[i])
            if nm == kernel} == {stage}


def test_scoped_trace_gaps_carry_program_spans(scoped):
    for name in ("runtime.pump", "runtime.admit", "runtime.noise",
                 "wave.segment", "wave.h2d", "wave.execute", "wave.d2h",
                 "wave.guard", "runtime.post"):
        assert scoped.spans.get(name), name
    assert len(scoped.spans["wave.execute"]) == len(
        scoped.spans["wave.segment"])
    gaps = scoped.idle_gaps(10_000)
    assert any("/" in label for label, _ in gaps)
    assert {label.split("/")[0] for label, _ in gaps} <= \
        set(tr.HOST_SPANS) | {"other"}
    idle = scoped.trace.window_s - scoped.trace.busy_s()
    assert sum(v for _, v in scoped.idle_by_span()) == pytest.approx(idle)
    assert scoped.seam_ms() > 0.0
