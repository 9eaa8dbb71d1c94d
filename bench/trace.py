"""Profiler trace -> device busy time, top device ops and labelled idle gaps.

Two steps, kept apart so a test can check the second on a recorded trace:

* :func:`load_xplane` reads the ``.xplane.pb`` the profiler wrote into a
  plain dict: the traced window (the benchmark's ``bench.window`` span),
  each device's op events, and the benchmark's own host spans.
* :func:`reduce` turns that dict into ``busy_s``, ``window_s``, the
  device ops that took most time, and the device's idle time grouped by
  the innermost benchmark span the host was in (engine spans first).

Times are nanoseconds on the trace's clock.  Busy time is the union of op
intervals inside the window, averaged over the devices.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from pathlib import Path

import numpy as np

WINDOW_SPAN = "bench.window"
HOST_PREFIXES = ("engine.", "server.", "bench.")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# the line of each device plane whose events are the ops that ran; the
# module line is the fallback
DEVICE_LINES = ("XLA Ops", "XLA Modules")
NO_SPAN = "(no bench span)"


_LAYOUT = re.compile(r"\{[^{}]*\}")
_OP = re.compile(r"^(.*?) ([a-z][\w-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")


def short_op(text: str) -> str:
    """``%fusion.86 = (s32[8192]{...}) fusion(...), kind=kLoop, ...`` ->
    ``fusion.86: fusion kLoop (s32[8192])``: the HLO name, opcode, fusion
    kind and result shape, without layouts or operands."""
    name, eq, rest = text.partition(" = ")
    if not eq:
        return text
    rest = _LAYOUT.sub("", _LAYOUT.sub("", rest))
    m = _OP.match(rest)
    if m is None:
        return name.lstrip("%")
    kind = _KIND.search(rest)
    op = m.group(2) + (f" {kind.group(1)}" if kind else "")
    return f"{name.lstrip('%')}: {op} {m.group(1)}"


def find_xplane(log_dir: str | os.PathLike) -> str:
    found = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    """The parts of a profiler trace the reduction reads."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, window = {}, [], None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            line = next((lines[n] for n in DEVICE_LINES if n in lines), None)
            if line is not None:
                devices[plane.name] = [
                    [short_op(ev.name), ev.start_ns, ev.duration_ns]
                    for ev in line.events]
        elif plane.name.startswith("/host:"):
            for i, ln in enumerate(plane.lines):
                for ev in ln.events:
                    name = ev.name
                    if name == WINDOW_SPAN:
                        window = [ev.start_ns, ev.start_ns + ev.duration_ns]
                    elif name.startswith(HOST_PREFIXES):
                        host.append([i, name, ev.start_ns, ev.duration_ns])
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    return {"window": window, "devices": devices, "host": host}


def _arrays(events, a, b):
    """Names, starts, ends of events clipped to [a, b] (dropping those
    wholly outside)."""
    if not events:
        return [], np.zeros(0), np.zeros(0)
    names = [e[0] for e in events]
    s = np.asarray([e[1] for e in events], np.float64)
    e = s + np.asarray([e[2] for e in events], np.float64)
    keep = (e > a) & (s < b)
    idx = np.nonzero(keep)[0]
    return ([names[i] for i in idx], np.clip(s[keep], a, b),
            np.clip(e[keep], a, b))


def _union(s, e):
    """Merged intervals of (s, e), sorted."""
    if s.size == 0:
        return s, e
    o = np.argsort(s, kind="stable")
    s, e = s[o], e[o]
    run = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > run[:-1]])
    starts = s[new]
    ends = np.maximum.reduceat(e, np.nonzero(new)[0])
    return starts, ends


def _leaves(names, s, e):
    """Events that contain no other event (a loop op's children carry its
    time; the loop itself would count it twice)."""
    o = np.lexsort((-(e - s), s))
    s_o, e_o = s[o], e[o]
    parent = np.zeros(s.size, bool)
    parent[o[:-1]] = s_o[1:] < e_o[:-1]
    return [n for n, p in zip(names, parent) if not p], s[~parent], e[~parent]


def _label_gaps(gs, ge, host):
    """Innermost benchmark span covering each gap's midpoint, engine spans
    first."""
    mid = (gs + ge) / 2
    best = [NO_SPAN] * mid.size
    best_rank = np.full(mid.size, np.inf)
    by_name: dict[str, list] = {}
    for _, name, s, d in host:
        by_name.setdefault(name, []).append((s, s + d))
    for name, iv in by_name.items():
        iv = np.asarray(sorted(iv), np.float64)
        # spans of one name on one thread do not overlap; across threads
        # they may, so test the latest-starting span before each midpoint
        j = np.searchsorted(iv[:, 0], mid, side="right") - 1
        ok = j >= 0
        ok[ok] = mid[ok] < iv[j[ok], 1]
        dur = np.where(ok, iv[np.maximum(j, 0), 1] - iv[np.maximum(j, 0), 0],
                       np.inf)
        rank = dur + (0.0 if name.startswith("engine.") else 1e18)
        better = ok & (rank < best_rank)
        best_rank[better] = rank[better]
        for k in np.nonzero(better)[0]:
            best[k] = name
    return best


def reduce(trace: dict, top: int = 10) -> dict:
    """``busy_s``, ``window_s`` and ``idle_share`` (averaged over the
    devices), with the ``device_ops`` that took most time and the
    ``idle_gaps`` by what the host was doing, each as [name, seconds]."""
    a, b = trace["window"]
    window = (b - a) / 1e9
    if not trace["devices"]:
        raise ValueError("trace has no device ops")
    busy, ops, gaps = [], {}, {}
    n = len(trace["devices"])
    for events in trace["devices"].values():
        names, s, e = _arrays(events, a, b)
        us, ue = _union(s, e)
        busy.append(float((ue - us).sum()) / 1e9)
        ln, ls, le = _leaves(names, s, e)
        for name, d in zip(ln, (le - ls) / 1e9):
            ops[name] = ops.get(name, 0.0) + d / n
        gs = np.concatenate([[a], ue])
        ge = np.concatenate([us, [b]])
        pos = ge > gs
        gs, ge = gs[pos], ge[pos]
        for label, d in zip(_label_gaps(gs, ge, trace["host"]),
                            (ge - gs) / 1e9):
            gaps[label] = gaps.get(label, 0.0) + d / n
    busy_s = sum(busy) / n
    rank = lambda d: sorted(([k, float(v)] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_s, "window_s": window,
            "idle_share": 1.0 - busy_s / window,
            "device_ops": rank(ops), "idle_gaps": rank(gaps)}


def trim(trace: dict, seconds: float) -> dict:
    """The first ``seconds`` of a trace's window, for a test fixture."""
    a = trace["window"][0]
    b = min(trace["window"][1], a + seconds * 1e9)
    cut = lambda evs, i: [ev for ev in evs if ev[i] < b and ev[i] + ev[i + 1] > a]
    return {"window": [a, b],
            "devices": {k: cut(v, 1) for k, v in trace["devices"].items()},
            "host": cut(trace["host"], 2)}


def save(trace: dict, path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load(path: Path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)
