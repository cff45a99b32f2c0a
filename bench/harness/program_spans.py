"""The program's own host spans in a profiler trace.

The program marks its host work with ``jax.profiler.TraceAnnotation``
spans whose names start with ``repro.`` (``repro.sched.admit``,
``repro.solve.base``, ...; docs/SERVING.md lists them), each on the
host thread that ran it and, for a span that served requests, with
their ids as ``rid``. They sit on the host's clock, as the benchmark's
``bench.*`` spans do, so they line up with a :class:`traces.Summary`
whose device operations were shifted onto that clock.

This module reads them from a trace file and names idle gaps by them:
a gap that no ``bench.*`` span covers goes to the program span with
the most self time (its time less its children's) in the gap, and to
``"unannotated"`` only where none is there. :func:`traces.reduce_dir`
does not call it: the benchmark's result line and its ``idle_gaps`` are
as ``traces.py`` makes them.

    python3 bench/harness/program_spans.py TRACE.xplane.pb

prints, for the trace's ``bench.window``, the seconds and count of
each program span and the idle time of device 0 by what the host was
doing, as JSON.
"""
from __future__ import annotations

import bisect
import collections
import json
import os
import sys

PREFIX = "repro."


def read(path: str) -> list:
    """``(name, thread line, start, end, rid)`` of every program span
    in the trace at ``path`` (a directory, or an ``.xplane.pb`` file),
    in seconds on the host's clock; ``rid`` is the span's request ids
    as a string, or None."""
    from jax.profiler import ProfileData

    from harness import traces
    data = ProfileData.from_file(traces._find(path))
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    rid = dict(ev.stats).get("rid")
                    out.append((ev.name, i, ev.start_ns * 1e-9,
                                ev.end_ns * 1e-9,
                                None if rid is None else str(rid)))
    return out


def seconds(spans: list, window: tuple, names) -> tuple:
    """``(seconds, count)`` of the spans named one of ``names``, each
    cut to ``window``; nested spans of one name each count."""
    names = {names} if isinstance(names, str) else set(names)
    w0, w1 = window
    sel = [(s, e) for n, _, s, e, _ in spans
           if n in names and e > w0 and s < w1]
    return sum(min(e, w1) - max(s, w0) for s, e in sel), len(sel)


def self_pieces(spans: list) -> list:
    """Cut one thread's nested spans ``(start, end, name)`` into the
    pieces where each is the innermost open span, sorted by start."""
    pieces, stack = [], []          # stack: [start, end, name, cursor]

    def close(top):
        if top[1] > top[3]:
            pieces.append((top[3], top[1], top[2]))
        if stack:
            stack[-1][3] = max(stack[-1][3], top[1])

    for s, e, n in sorted(spans, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            top = stack[-1]
            if s > top[3]:
                pieces.append((top[3], s, top[2]))
            top[3] = max(top[3], s)
        stack.append([s, e, n, s])
    while stack:
        close(stack.pop())
    return sorted(pieces)


class GapNamer:
    """Names a host interval by the program span with the most self
    time in it, over every thread."""

    def __init__(self, spans: list):
        by_line = collections.defaultdict(list)
        for n, line, s, e, _ in spans:
            by_line[line].append((s, e, n))
        self._lines = []
        for line_spans in by_line.values():
            pieces = self_pieces(line_spans)
            self._lines.append(([pe for _, pe, _ in pieces], pieces))

    def __call__(self, s: float, e: float) -> str:
        cover = collections.Counter()
        for ends, pieces in self._lines:
            for ps, pe, name in pieces[bisect.bisect_right(ends, s):]:
                if ps >= e:
                    break
                cover[name] += min(e, pe) - max(s, ps)
        if not cover:
            return "unannotated"
        return cover.most_common(1)[0][0]


def gaps(summary, spans: list, dev: int = 0) -> list:
    """:meth:`traces.Summary.gaps`, with the gaps it calls
    ``"unannotated"`` named by the program spans where they cover them:
    ``(name, seconds)``."""
    name = GapNamer(spans)
    edges = [summary.window[0]]
    for s, e in summary.busy(dev):
        edges += [s, e]
    edges.append(summary.window[1])
    out = []
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            label = summary._host_doing(s, e)
            if label == "unannotated":
                label = name(s, e)
            out.append((label, e - s))
    return out


def idle_gaps(summary, spans: list, top: int = 10) -> list:
    """Device 0's idle seconds by what the host was doing, most first,
    as ``[[name, seconds], ...]``."""
    idle = collections.Counter()
    for label, sec in gaps(summary, spans):
        idle[label] += sec
    return [[k, v] for k, v in idle.most_common(top)]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    from harness import traces
    summary = traces.reduce_dir(argv[0])
    spans = read(argv[0])
    names = sorted({n for n, *_ in spans})
    out = {"window_s": summary.window_s,
           "spans": {n: dict(zip(("seconds", "count"),
                                 seconds(spans, summary.window, n)))
                     for n in names},
           "idle_gaps": idle_gaps(summary, spans)}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
