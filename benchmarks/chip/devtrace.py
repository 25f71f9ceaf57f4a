"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
In it, each TPU chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops``
holds one event per operation run on the chip, and the host plane holds
the benchmark's own spans, ``bench.<kind>`` (``bench.Spans``), on the same
clock. The traced window is the span ``bench.window``.

``reduce`` gives, over that window and averaged over the chips that ran
anything: the seconds in which an operation ran (the union of the op
intervals, so that overlapping ops count once), that busy time inside the
spans of each kind, the idle gaps attributed to the span kind the host was
in, and the operations that took most time.
"""
from __future__ import annotations

import bisect
import pathlib
import re

import numpy as np

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PREFIX = "bench."


def xplane(trace_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read(path) -> dict:
    """The raw events: per chip its ops and programs as (start_ns, end_ns,
    name), and the benchmark's spans as (kind, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, modules, spans = {}, {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.fullmatch(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dst = ops if line.name == OPS_LINE else modules
                    dst[chip] = [(e.start_ns, e.start_ns + e.duration_ns,
                                  e.name) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append((e.name[len(PREFIX):], e.start_ns,
                                      e.start_ns + e.duration_ns))
    return {"ops": ops, "modules": modules, "spans": spans}


def union(intervals, lo: float, hi: float) -> np.ndarray:
    """Disjoint, sorted (n, 2) union of ``intervals`` clipped to [lo, hi]."""
    if not len(intervals):
        return np.zeros((0, 2))
    iv = np.asarray([(a, b) for a, b, *_ in intervals], float)
    iv = np.clip(iv, lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if not len(iv):
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out)


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two disjoint sorted unions."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            total += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


def gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The complement of ``busy`` in [lo, hi]."""
    edges = np.concatenate([[lo], busy.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def _labelled(ops: list, modules: list) -> list:
    """Each op as (start, end, "<program>:<op>"): the program whose run
    on the chip holds the op's start, and the op's short HLO name."""
    mods = sorted(modules)
    starts = [m[0] for m in mods]
    out = []
    for a, b, name in ops:
        i = bisect.bisect_right(starts, a) - 1
        prog = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
        out.append((a, b, f"{prog}:{name.split(' = ')[0]}"))
    return out


def _top(totals: dict, n: int = 10) -> list:
    return sorted(([k, v] for k, v in totals.items() if v > 0),
                  key=lambda kv: -kv[1])[:n]


def reduce(raw: dict) -> dict | None:
    """Numbers of the window ``bench.window``, in seconds; None where the
    trace holds no window or no chip ran anything in it."""
    windows = [(a, b) for k, a, b in raw["spans"] if k == "window"]
    if not windows:
        return None
    w0, w1 = windows[0]
    kinds = {}
    for k, a, b in raw["spans"]:
        if k != "window" and b > w0 and a < w1:
            kinds.setdefault(k, []).append((a, b))
    span_union = {k: union(v, w0, w1) for k, v in kinds.items()}
    chips = {c: union(ev, w0, w1) for c, ev in raw["ops"].items()}
    chips = {c: u for c, u in chips.items() if len(u)}
    if not chips:
        return None
    n = len(chips)
    busy_in = {k: 0.0 for k in kinds}
    idle_by = {}
    op_time = {}
    busy = 0.0
    for c, u in chips.items():
        busy += float(np.sum(u[:, 1] - u[:, 0]))
        for k, su in span_union.items():
            busy_in[k] += overlap(u, su)
        idle = gaps(u, w0, w1)
        left = float(np.sum(idle[:, 1] - idle[:, 0]))
        for k, su in span_union.items():
            got = overlap(idle, su)
            idle_by[k] = idle_by.get(k, 0.0) + got
            left -= got
        idle_by["(no span)"] = idle_by.get("(no span)", 0.0) + max(left, 0.0)
        for a, b, name in _labelled(raw["ops"][c], raw["modules"].get(c, [])):
            a, b = max(a, w0), min(b, w1)
            if b > a:
                op_time[name] = op_time.get(name, 0.0) + (b - a)
    s = 1e-9 / n            # ns summed over chips -> seconds per chip
    return {
        "chips": n,
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy * s,
        "busy_in_s": {k: v * s for k, v in busy_in.items()},
        "span_count": {k: len(v) for k, v in kinds.items()},
        "device_ops": [[k, v * s] for k, v in _top(op_time)],
        "idle_gaps": [[k, v * s] for k, v in _top(idle_by)],
    }
