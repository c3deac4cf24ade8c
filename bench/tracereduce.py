"""From a profiler trace to device busy time, kernel time and idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX.  Device operations are the events of each device plane's
``XLA Ops`` line; host spans are the harness's ``TraceAnnotation``s
("window", "submit", "pump", "wait") on the host plane.  Both are on
the profiler's one clock, so an idle gap on the device can be labelled
with what the host was doing meanwhile.

* busy: the union of the device operations' intervals inside the
  traced window, averaged over the devices that ran anything;
* kernel time: the summed durations of the operations of one HLO
  instruction name (``%support_sqdist.6 = ...`` is ``support_sqdist``:
  a Pallas kernel's instruction takes its wrapper's name);
* top operations: device time per instruction name, each operation's
  own time only (a ``while`` loop's time less that of its body);
* idle gaps: the complement of the busy union inside the window, each
  labelled by the host annotation that overlaps it most.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

import numpy as np

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "window"
HOST_SPANS = ("submit", "pump", "wait")


@dataclasses.dataclass
class DeviceOps:
    name: str
    start: np.ndarray          # ns
    end: np.ndarray            # ns
    names: list[str]           # HLO instruction names, ".N" suffix dropped


@dataclasses.dataclass
class Reduced:
    window: tuple[float, float]        # ns, on the trace clock
    devices: list[DeviceOps]
    host: dict[str, list[tuple[float, float]]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clipped(self, d: DeviceOps) -> tuple[np.ndarray, np.ndarray]:
        lo, hi = self.window
        s = np.clip(d.start, lo, hi)
        e = np.clip(d.end, lo, hi)
        keep = e > s
        return s[keep], e[keep]

    def _clipped_all(self, d: DeviceOps) -> tuple[np.ndarray, np.ndarray]:
        lo, hi = self.window
        return np.clip(d.start, lo, hi), np.clip(d.end, lo, hi)

    def busy_intervals(self, d: DeviceOps) -> list[tuple[float, float]]:
        s, e = self._clipped(d)
        order = np.argsort(s, kind="stable")
        out: list[list[float]] = []
        for a, b in zip(s[order], e[order]):
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([float(a), float(b)])
        return [(a, b) for a, b in out]

    def busy_s(self) -> float:
        """Union of device-op intervals in the window, in seconds,
        averaged over the devices that ran an operation."""
        per = [sum(b - a for a, b in self.busy_intervals(d)) * 1e-9
               for d in self.devices if d.start.size]
        return float(np.mean(per)) if per else 0.0

    def idle_share(self) -> float | None:
        busy = self.busy_s()
        if busy <= 0.0 or self.window_s <= 0.0:
            return None
        return 1.0 - busy / self.window_s

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of every operation named ``kernel``, summed
        over devices (inside the window)."""
        lo, hi = self.window
        total = 0.0
        for d in self.devices:
            for s, e, nm in zip(d.start, d.end, d.names):
                if nm == kernel:
                    total += max(0.0, min(e, hi) - max(s, lo))
        return total * 1e-9

    def top_ops(self, count: int = 10) -> list[list]:
        """The ``count`` instruction names that took most device time,
        counting each operation's own time (nested operations, such as
        a loop's body, are taken out of their parent)."""
        acc: dict[str, float] = {}
        for d in self.devices:
            s, e = self._clipped_all(d)
            own = e - s
            stack: list[int] = []
            for i in np.argsort(s, kind="stable"):
                while stack and e[stack[-1]] <= s[i]:
                    stack.pop()
                if stack:
                    own[stack[-1]] -= own[i] if own[i] > 0 else 0.0
                stack.append(i)
            for nm, t in zip(d.names, own):
                acc[nm] = acc.get(nm, 0.0) + max(0.0, t) * 1e-9
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:count]
        return [[k, float(v)] for k, v in top]

    def idle_gaps(self, count: int = 10) -> list[list]:
        """The ``count`` longest idle gaps of the first busy device,
        each labelled by the host span that overlaps it most."""
        dev = next((d for d in self.devices if d.start.size), None)
        if dev is None:
            return []
        lo, hi = self.window
        gaps, prev = [], lo
        for a, b in self.busy_intervals(dev):
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        if hi > prev:
            gaps.append((prev, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:count]:
            best, best_ov = "other", 0.0
            for label, spans in self.host.items():
                ov = sum(max(0.0, min(b, e) - max(a, s)) for s, e in spans)
                if ov > best_ov:
                    best, best_ov = label, ov
            out.append([best, (b - a) * 1e-9])
        return out


def op_name(hlo_text: str) -> str:
    """The instruction name without its ``.N`` suffix:
    ``%support_sqdist.6 = f32[...] custom-call(...)`` -> ``support_sqdist``.
    A plain ``fusion`` also names its output type, with the leading
    dimension left out (``fusion f32[*,3072]`` is a gather of rows)."""
    head, _, rest = hlo_text.partition(" = ")
    head = head.lstrip("%")
    stem, _, suffix = head.rpartition(".")
    name = stem if stem and suffix.isdigit() else head
    m = re.match(r"(\w+)\[\d+(,[\d,]*)?\]", rest)
    if name == "fusion" and m:
        name += f" {m.group(1)}[*{m.group(2) or ''}]"
    return name


def find_trace(trace_dir: str | Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce(path: str | Path) -> Reduced:
    """Read one ``.xplane.pb`` into a :class:`Reduced`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: list[DeviceOps] = []
    host: dict[str, list[tuple[float, float]]] = {k: [] for k in HOST_SPANS}
    window = None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            st, en, names = [], [], []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    st.append(ev.start_ns)
                    en.append(ev.start_ns + ev.duration_ns)
                    names.append(op_name(ev.name))
            devices.append(DeviceOps(plane.name, np.asarray(st, float),
                                     np.asarray(en, float), names))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    span = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name == WINDOW:
                        window = span
                    elif ev.name in host:
                        host[ev.name].append(span)
    if window is None:
        raise ValueError(f"{path}: no '{WINDOW}' annotation on the host")
    return Reduced(window=window, devices=devices, host=host)
