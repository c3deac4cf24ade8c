"""What the program itself writes into a traced run's profiler trace.

Two things, on the profiler's one clock:

* the engine stage of every device operation.  ``repro.core.engine``
  wraps each stage of the step in a ``jax.named_scope`` (``stage.screen``,
  ``stage.ivf_screen``, ``stage.rerank``, ``stage.aggregate``,
  ``stage.fused_step``), so an operation's JAX name path, the ``tf_op``
  stat of its event metadata, holds its stage:
  ``jit(traced)/while/body/closed_call/stage.rerank/jit(support_sqdist)/
  gather:``.  ``ProfileData`` does not expose metadata stats, so
  :func:`op_paths` reads them from the file's protobuf wire format;
* the program's host spans.  ``repro.obs.trace`` mirrors every span of
  an enabled tracer into a ``jax.profiler.TraceAnnotation``, so
  ``runtime.pump``, ``runtime.admit``, ``runtime.noise``,
  ``wave.segment``, ``wave.h2d``, ``wave.execute``, ``wave.d2h``,
  ``wave.guard``, ``runtime.post`` and ``gc.collect`` are events of the
  host plane, beside the harness's own "submit", "pump" and "wait".

The reduction works on the trace the harness left of the run
(``harness.OUT_DIR/trace/<cell>``) and on its :class:`tracereduce.Reduced`:

* stage time: each operation's own time (a loop's body taken out of the
  loop, as ``Reduced.top_ops`` counts it), summed by stage, with the
  operations in no stage under ``UNSCOPED``;
* idle gaps labelled ``<harness span>/<program span>`` (``pump/wave.d2h``):
  the harness span that overlaps the gap most, then the innermost
  (shortest) program span that covers half of it or more, else the
  program span that covers most of it.  A gap no program span overlaps
  keeps the harness label alone.

A program that writes neither (no stage scopes, no mirrored spans)
reads as no stage time and no program span, and the metrics built on
them read None.  Run as a script on a trace file, it prints the stage
breakdown and the labelled idle time as JSON:

  python3 bench/programtrace.py .bench_out/trace/<cell>
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import costs, layers, tracereduce  # noqa: E402

STAGE_PREFIX = "stage."
UNSCOPED = "unscoped"
TF_OP = "tf_op"
PROGRAM_SPANS = ("runtime.", "wave.", "gc.", "engine.", "dispatch.",
                 "plan.")
MODULES_LINE = "XLA Modules"


def stage_of(path: str) -> str:
    """The innermost ``stage.*`` component of a ``tf_op`` name path, or
    "" when the operation is in no stage."""
    path = path.rpartition(":")[0] if ":" in path else path
    for part in reversed(path.split("/")):
        if part.startswith(STAGE_PREFIX):
            return part
    return ""


# -- the protobuf wire format of an XSpace, for what ProfileData hides --------
# XSpace.planes = 1; XPlane: name = 2, lines = 3, event_metadata = 4 and
# stat_metadata = 5 (maps: key = 1, value = 2); XLine: name = 2,
# events = 4; XEvent.metadata_id = 1; XEventMetadata.stats = 5;
# XStatMetadata.name = 2; XStat: metadata_id = 1, str_value = 5,
# ref_value = 7 (the id of a stat metadata whose name is the string).
def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of a message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, val


def _map_entry(buf) -> tuple[int, memoryview]:
    key, val = 0, memoryview(b"")
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane_paths(plane) -> tuple[str, list[str]]:
    """(plane name, the ``tf_op`` of each ``XLA Ops`` event in order)."""
    name, lines, ev_meta, stat_names = "", [], {}, {}
    for f, v in _fields(plane):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            k, meta = _map_entry(v)
            ev_meta[k] = meta
        elif f == 5:
            k, meta = _map_entry(v)
            stat_names[k] = next((bytes(x).decode() for g, x in
                                  _fields(meta) if g == 2), "")
    if not name.startswith(tracereduce.DEVICE_PREFIX):
        return name, []
    tf_id = next((k for k, nm in stat_names.items() if nm == TF_OP), None)
    paths: dict[int, str] = {}
    for k, meta in ev_meta.items():
        for f, stat in _fields(meta):
            if f != 5:
                continue
            st = dict(_fields(stat))
            if tf_id is None or st.get(1) != tf_id:
                continue
            if 5 in st:
                paths[k] = bytes(st[5]).decode()
            elif 7 in st:
                paths[k] = stat_names.get(st[7], "")
    out: list[str] = []
    for line in lines:
        fl = list(_fields(line))
        if next((bytes(v).decode() for f, v in fl if f == 2),
                "") != tracereduce.OPS_LINE:
            continue
        for f, ev in fl:
            if f == 4:
                mid = next((v for g, v in _fields(ev) if g == 1), 0)
                out.append(paths.get(mid, ""))
    return name, out


def op_paths(path: str | Path) -> dict[str, list[str]]:
    """``{device plane: [tf_op of each XLA Ops event, in file order]}``
    ("" where an operation has none).  The order is the order in which
    ``ProfileData`` yields the events, so the lists align with the
    operations of ``tracereduce.reduce``."""
    buf = memoryview(Path(path).read_bytes())
    out = {}
    for f, plane in _fields(buf):
        if f == 1:
            name, paths = _plane_paths(plane)
            if name.startswith(tracereduce.DEVICE_PREFIX):
                out[name] = paths
    return out


def program_spans(path: str | Path) -> tuple[dict, dict]:
    """The program's spans on the host plane, by name, and the program
    executions of each device plane (its ``XLA Modules`` line): both as
    (start, end) ns."""
    from jax.profiler import ProfileData
    spans: dict[str, list[tuple[float, float]]] = {}
    modules: dict[str, list[tuple[float, float]]] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith(tracereduce.DEVICE_PREFIX):
            modules[plane.name] = [
                (ev.start_ns, ev.start_ns + ev.duration_ns)
                for line in plane.lines if line.name == MODULES_LINE
                for ev in line.events]
        if plane.name != tracereduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_SPANS):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return spans, modules


def device_shift(executions, modules) -> float:
    """The smallest shift (ns, >= 0) of the device clock that starts no
    program before the host dispatched it: each ``wave.execute`` span
    against the program execution that overlaps it most.  The profiler
    aligns the two clocks only to about a millisecond, and a device
    program that seems to start before its dispatch shows the error."""
    if not executions or not modules:
        return 0.0
    ms = np.asarray(modules, float)
    shift = 0.0
    for a, b in executions:
        ov = np.minimum(ms[:, 1], b) - np.maximum(ms[:, 0], a)
        j = int(np.argmax(ov))
        if ov[j] > 0.0:
            shift = max(shift, a - ms[j, 0])
    return shift


# -- the reduction ----------------------------------------------------------
@dataclasses.dataclass
class Program:
    trace: tracereduce.Reduced
    stages: list[list[str]]            # per device of ``trace``, per op
    spans: dict[str, list[tuple[float, float]]]
    shift: float = 0.0                 # ns added to device times to label

    def stage_times(self) -> dict[str, float]:
        """Own device seconds by stage, summed over devices."""
        acc: dict[str, float] = {}
        lo, hi = self.trace.window
        for d, stages in zip(self.trace.devices, self.stages):
            s, e = np.clip(d.start, lo, hi), np.clip(d.end, lo, hi)
            own = e - s
            stack: list[int] = []
            for i in np.argsort(s, kind="stable"):
                while stack and e[stack[-1]] <= s[i]:
                    stack.pop()
                if stack:
                    own[stack[-1]] -= own[i] if own[i] > 0 else 0.0
                stack.append(i)
            for st, t in zip(stages, own):
                key = st or UNSCOPED
                acc[key] = acc.get(key, 0.0) + max(0.0, float(t)) * 1e-9
        return acc

    def stage_s(self, stage: str) -> float:
        """Own device seconds of the operations in ``stage.<stage>``."""
        return self.stage_times().get(STAGE_PREFIX + stage, 0.0)

    def _gaps(self) -> list[tuple[float, float]]:
        """Idle intervals of the first busy device, longest first (the
        gaps ``Reduced.idle_gaps`` labels)."""
        dev = next((d for d in self.trace.devices if d.start.size), None)
        if dev is None:
            return []
        lo, hi = self.trace.window
        gaps, prev = [], lo
        for a, b in self.trace.busy_intervals(dev):
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        if hi > prev:
            gaps.append((prev, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        return gaps

    def _index(self):
        """Program spans sorted by start: (names, starts, ends, longest)."""
        flat = sorted((s, e, nm) for nm, spans in self.spans.items()
                      for s, e in spans)
        if not flat:
            return [], np.zeros(0), np.zeros(0), 0.0
        st = np.asarray([s for s, _, _ in flat], float)
        en = np.asarray([e for _, e, _ in flat], float)
        return [nm for _, _, nm in flat], st, en, float((en - st).max())

    def _label(self, a: float, b: float, index) -> str:
        """The label of the device gap (a, b), device clock."""
        a, b = a + self.shift, b + self.shift
        best, best_ov = "other", 0.0
        for label, spans in self.trace.host.items():
            ov = sum(max(0.0, min(b, e) - max(a, s)) for s, e in spans)
            if ov > best_ov:
                best, best_ov = label, ov
        names, st, en, longest = index
        lo = np.searchsorted(st, a - longest, side="left")
        hi = np.searchsorted(st, b, side="left")
        ov = np.minimum(en[lo:hi], b) - np.maximum(st[lo:hi], a)
        if not ov.size or ov.max() <= 0.0:
            return best
        half = ov >= 0.5 * (b - a)
        if half.any():
            pick = int(np.argmin(np.where(half, en[lo:hi] - st[lo:hi],
                                          np.inf)))
        else:
            pick = int(np.argmax(ov))
        return f"{best}/{names[lo + pick]}"

    def idle_gaps(self, count: int = 10) -> list[list]:
        """The ``count`` longest idle gaps, each with its
        ``<harness span>/<program span>`` label."""
        index = self._index()
        return [[self._label(a, b, index), (b - a) * 1e-9]
                for a, b in self._gaps()[:count]]

    def idle_by_span(self) -> list[list]:
        """Idle seconds split exactly by the innermost program span the
        host was in at each instant of each gap ("-" outside every
        program span), the largest first."""
        names, st, en, longest = self._index()
        acc: dict[str, float] = {}
        for a, b in self._gaps():
            a, b = a + self.shift, b + self.shift
            lo = np.searchsorted(st, a - longest, side="left")
            hi = np.searchsorted(st, b, side="left")
            s, e = st[lo:hi], en[lo:hi]
            live = e > a
            s, e, idx = s[live], e[live], np.arange(lo, hi)[live]
            cuts = np.unique(np.clip(np.concatenate([[a, b], s, e]), a, b))
            for c0, c1 in zip(cuts[:-1], cuts[1:]):
                mid = 0.5 * (c0 + c1)
                cover = (s <= mid) & (e >= mid)
                if cover.any():
                    k = idx[cover][int(np.argmin((e - s)[cover]))]
                    key = names[k]
                else:
                    key = "-"
                acc[key] = acc.get(key, 0.0) + (c1 - c0) * 1e-9
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])]

    def seam_ms(self) -> float | None:
        """Mean host time from the end of one ``wave.execute`` span to
        the start of the next, on the profiler's clock."""
        ex = sorted(self.spans.get("wave.execute", []))
        if len(ex) < 2:
            return None
        return 1e-6 * float(np.mean([b[0] - a[1]
                                     for a, b in zip(ex, ex[1:])]))


def reduce(path: str | Path, trace: tracereduce.Reduced | None = None
           ) -> Program:
    """The program's part of the trace at ``path``; ``trace`` is its
    ``tracereduce.reduce`` when already made."""
    trace = tracereduce.reduce(path) if trace is None else trace
    paths = op_paths(path)
    stages = []
    for d in trace.devices:
        p = paths.get(d.name, [])
        if len(p) != len(d.names):
            raise ValueError(f"{path}: {d.name} has {len(d.names)} "
                             f"operations but {len(p)} in its wire format")
        stages.append([stage_of(x) for x in p])
    spans, modules = program_spans(path)
    dev = next((d for d in trace.devices if d.start.size), None)
    shift = 0.0 if dev is None else device_shift(
        spans.get("wave.execute", []), modules.get(dev.name, []))
    return Program(trace, stages, spans, shift)


_CACHE: dict = {}


def of_run(run) -> Program | None:
    """The program's part of a traced run's trace (read once per trace
    file), or None for an untraced run."""
    if run.trace is None:
        return None
    from bench.harness import OUT_DIR
    path = tracereduce.find_trace(OUT_DIR / "trace" / run.cell.name)
    key = (str(path), path.stat().st_mtime_ns, id(run.trace))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = reduce(path, run.trace)
    return _CACHE[key]


def stage_roofline(run, stage: str):
    """An engine stage's share of its roofline: the least time of its
    work (``bench/costs.stage_costs`` per active row at each traced
    segment-step's m_t, k_t, the sizes ``layers.kernel_roofline`` uses)
    over the own device time of the operations in its ``stage.<stage>``
    scope: gathers, ``top_k`` and kernels alike."""
    prog = of_run(run)
    if run.peak is None or prog is None or not run.segments:
        return None
    dev_s = prog.stage_s(stage)
    if dev_s <= 0.0:
        return None
    least = 0.0
    for st, active in layers._row_steps(run):
        c = costs.stage_costs(run.cell.config, st.m, st.k, active)[stage]
        least += costs.least_time(c["flops"], c["bytes"], run.peak)
    return 100.0 * least / dev_s


def seam_ms(run):
    """Mean host time between segments, from the program's own
    ``wave.execute`` spans in the trace."""
    prog = of_run(run)
    return None if prog is None else prog.seam_ms()


def main(argv=None) -> int:
    import json
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.strip().splitlines()[-1], file=sys.stderr)
        return 2
    where = Path(args[0])
    path = where if where.is_file() else tracereduce.find_trace(where)
    prog = reduce(path)
    busy = prog.trace.busy_s()
    print(json.dumps({
        "window_s": prog.trace.window_s, "busy_s": busy,
        "stages": sorted(prog.stage_times().items(), key=lambda kv: -kv[1]),
        "idle_gaps": prog.idle_gaps(),
        "idle_by_span": prog.idle_by_span(),
        "seam_ms": prog.seam_ms(),
        "device_shift_ms": prog.shift * 1e-6,
        "spans": {k: len(v) for k, v in sorted(prog.spans.items())}},
        indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
