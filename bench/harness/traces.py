"""Reduce a JAX profiler trace to device time.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
:func:`jax.profiler.ProfileData` reads it. Each chip is a plane named
``/device:TPU:<i>``. Its ``XLA Ops`` line holds one event per operation
run on the device, named by the operation's HLO text
(``%panel_update.3 = (f32[768,256]...) custom-call(...)``): a Pallas
kernel is a ``custom-call`` named after the function that made the
``pallas_call`` (``panel_update``, ``potrf_leaf``, ``tri_inv_leaf``,
``residual_fused``, ``qgemm``). Its ``XLA Modules`` line holds one
event per program run. The host is the plane ``/host:CPU``, one line
per thread; the benchmark's own spans (``jax.profiler.TraceAnnotation``)
are its events whose names start with ``bench.``, and each program
launch is a ``tpu::System::Execute`` event.

The device's clock ran about 1.2 ms behind the host's in the traces
seen on a TPU v5e, so device times are shifted onto the host's clock by
the largest (launch end on the host - start of the same program on the
device), pairing launches and programs in order. A program queued
behind others starts later than its launch, so its gap is smaller by
its wait; the largest gap is that of a program launched onto an idle
device, which is the clocks' own offset.

From these:

* the window is the ``bench.window`` span;
* busy time is the union of the device's operation intervals inside
  the window (idle share is one less busy over window);
* a kernel's time is the summed duration of the operations whose HLO
  name, without XLA's number, is the kernel's (:func:`Summary.op_seconds`);
* each idle gap of device 0 inside the window is put down to the
  benchmark span that covers most of it on the host, or to
  ``"unannotated"``.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_HLO_NAME = re.compile(r"^%?([\w.\-]+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LAUNCH = "tpu::System::Execute"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Op:
    name: str          # HLO operation name, e.g. ``panel_update.3``
    start: float       # seconds
    end: float


@dataclasses.dataclass
class Summary:
    """What one traced window holds."""

    window: tuple                  # (start, end) seconds
    ops: dict                      # device index -> [Op] inside the window
    spans: list                    # (name, start, end) benchmark host spans

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy(self, dev: int = 0) -> list:
        """Union of the device's operation intervals, sorted."""
        merged: list = []
        for o in sorted(self.ops.get(dev, []), key=lambda o: o.start):
            if merged and o.start <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], o.end)
            else:
                merged.append([o.start, o.end])
        return merged

    def busy_s_of(self, dev: int) -> float:
        return sum(e - s for s, e in self.busy(dev))

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips traced."""
        devs = sorted(self.ops) or [0]
        return sum(self.busy_s_of(d) for d in devs) / len(devs)

    def idle_pct(self, dev: int = 0) -> float:
        return 100.0 * (1.0 - self.busy_s_of(dev) / self.window_s)

    def op_seconds(self, kinds, dev: int = 0) -> tuple:
        """``(seconds, count)`` of device ``dev``'s operations whose
        :func:`op_kind` is one of ``kinds``."""
        kinds = {kinds} if isinstance(kinds, str) else set(kinds)
        sel = [o for o in self.ops.get(dev, []) if op_kind(o) in kinds]
        return sum(o.end - o.start for o in sel), len(sel)

    def gaps(self, dev: int = 0) -> list:
        """Idle gaps of device ``dev`` inside the window, each as
        ``(host span name, seconds)``."""
        edges = [self.window[0]]
        for s, e in self.busy(dev):
            edges += [s, e]
        edges.append(self.window[1])
        out = []
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                out.append((self._host_doing(s, e), e - s))
        return out

    def _host_doing(self, s: float, e: float) -> str:
        cover = collections.Counter()
        for name, hs, he in self.spans:
            if name == WINDOW_SPAN:
                continue
            ov = min(e, he) - max(s, hs)
            if ov > 0:
                cover[name] += ov
        if not cover:
            return "unannotated"
        return cover.most_common(1)[0][0]

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time on device 0, by
        kernel or operation kind, and the idle time by what the host was
        doing, each at most ``top`` entries of ``[name, seconds]``."""
        by_op = collections.Counter()
        for o in self.ops.get(0, []):
            by_op[op_kind(o)] += o.end - o.start
        idle = collections.Counter()
        for name, sec in self.gaps(0):
            idle[name] += sec
        return {"device_ops": [[k, v] for k, v in by_op.most_common(top)],
                "idle_gaps": [[k, v] for k, v in idle.most_common(top)]}


def op_kind(o: Op) -> str:
    """A stable name for an operation: its HLO name without the number
    XLA appends (``panel_update.3`` -> ``panel_update``)."""
    return re.sub(r"\.\d+$", "", o.name)


def _find(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def reduce_dir(path: str, chips: int = 1) -> Summary:
    """Read the trace under ``path`` (a profile directory or an
    ``.xplane.pb`` file) and reduce it to a :class:`Summary` of its
    ``bench.window`` span, for the first ``chips`` devices."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(_find(path))
    spans, raw = [], collections.defaultdict(list)
    modules, launches = collections.defaultdict(list), []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m and int(m.group(1)) < chips:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[dev] += [ev.start_ns * 1e-9 for ev in line.events]
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    raw[dev].append(Op(_HLO_NAME.match(ev.name).group(1),
                                       ev.start_ns * 1e-9,
                                       ev.end_ns * 1e-9))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      ev.end_ns * 1e-9))
                    elif ev.name == LAUNCH:
                        launches.append(ev.end_ns * 1e-9)
    shift = _clock_shift(sorted(launches), sorted(modules.get(0, [])))
    wins = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    w0, w1 = wins[0]
    ops = {}
    for d, lst in raw.items():
        ops[d] = [Op(o.name, max(o.start + shift, w0),
                     min(o.end + shift, w1)) for o in lst
                  if o.end + shift > w0 and o.start + shift < w1]
    return Summary(window=(w0, w1), ops=ops, spans=spans)


def _clock_shift(launches: list, starts: list) -> float:
    """Seconds to add to device 0's times to put them on the host's
    clock: the largest gap from a launch's end on the host to the start
    of the program on the device, pairing the two in order (0 where the
    trace holds neither). Smaller gaps are programs that waited in the
    device's queue."""
    k = min(len(launches), len(starts))
    if not k:
        return 0.0
    return max(h - d for h, d in zip(launches[-k:], starts[-k:]))
