"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers: busy and idle time of the traced window, the device operations
that took most time, the idle gaps named by what the host was doing, and
the device time of each compiled program (module).

Device operations are the events of the ``XLA Ops`` line of each
``/device:`` plane (a TPU), and programs those of its ``XLA Modules``
line.  A trace with no such plane (the CPU backend, used by the
self-check in ``tests/test_xplane.py``) has its operations on host
threads instead: the events that carry an ``hlo_op`` stat, with the
program in their ``hlo_module`` stat.  The window is the host annotation
``bench.window``; an idle gap is named by the innermost of the host
annotations in ``names`` that covers its middle, or ``unannotated``.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

TOP = 10
_SUFFIX = re.compile(r"\(\d+\)$")


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def events(pd, names: set) -> dict:
    """Raw intervals (ns): per device its ``ops`` and ``modules`` as
    ``(name, start, end)``, and the host annotations in ``names``."""
    planes = list(pd.planes)
    on_device = any(p.name.startswith("/device:")
                    and any(ln.name == "XLA Ops" for ln in p.lines)
                    for p in planes)
    devices: dict = {}
    host: list = []
    for plane in planes:
        is_dev = plane.name.startswith("/device:")
        for line in plane.lines:
            if is_dev:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                rec = devices.setdefault(plane.name,
                                         {"ops": [], "modules": []})
                rec[key].extend((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns)
                                for ev in line.events)
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name in names:
                    host.append((ev.name, s, e))
                elif not on_device and e > s and ev.stats:
                    st = dict(ev.stats)
                    if "hlo_op" in st:
                        rec = devices.setdefault("/host:CPU", {
                            "ops": [], "modules": [], "runs": {}})
                        rec["ops"].append((ev.name, s, e))
                        run = rec["runs"].setdefault(
                            (str(st.get("hlo_module", "?")),
                             st.get("run_id")), [s, e])
                        run[0], run[1] = min(run[0], s), max(run[1], e)
    cpu = devices.get("/host:CPU")
    if cpu:      # one execution of a program spans all ops of its run
        cpu["modules"] = [(n, s, e) for (n, _), (s, e)
                          in cpu.pop("runs").items()]
    return {"devices": devices, "host": host}


def reduce(pd, names: set) -> dict:
    """The window's numbers, in seconds."""
    ev = events(pd, names)
    wins = [(s, e) for n, s, e in ev["host"] if n == "bench.window"]
    if not wins:
        raise ValueError("trace has no bench.window annotation")
    lo, hi = wins[0]
    devs = {k: v for k, v in ev["devices"].items() if v["ops"]}
    if not devs:
        raise ValueError("trace has no device operations")
    busy = []
    op_time: dict = defaultdict(float)
    mod_time: dict = defaultdict(lambda: [0, 0.0])
    gaps = None
    for name in sorted(devs):
        rec = devs[name]
        u = _union([[max(s, lo), min(e, hi)] for _, s, e in rec["ops"]
                    if e > lo and s < hi])
        busy.append(sum(e - s for s, e in u) * 1e-9)
        for n, c in _named_ops(rec, lo, hi):
            op_time[n] += c * 1e-9 / len(devs)
        for n, s, e in rec["modules"]:
            if lo <= (s + e) / 2 <= hi:
                m = mod_time[_SUFFIX.sub("", n)]
                m[0] += 1
                m[1] += (e - s) * 1e-9 / len(devs)
        if gaps is None:          # idle gaps of the first device
            gaps, prev = [], lo
            for s, e in u:
                if s > prev:
                    gaps.append((prev, s))
                prev = e
            if hi > prev:
                gaps.append((prev, hi))
    phases = [(n, s, e) for n, s, e in ev["host"] if n != "bench.window"]
    idle: dict = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [(pe - ps, n) for n, ps, pe in phases if ps <= mid <= pe]
        idle[min(cover)[1] if cover else "unannotated"] += (e - s) * 1e-9
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy) / len(busy)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "chips": len(devs),
        "device_ops": [[n, t] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[n, t] for n, t in sorted(
            idle.items(), key=lambda kv: -kv[1])[:TOP]],
        "modules": {n: {"count": c, "seconds": t}
                    for n, (c, t) in mod_time.items()},
        "phase_counts": {n: sum(1 for p in phases if p[0] == n)
                         for n in {p[0] for p in phases}},
    }


def _named_ops(rec: dict, lo: float, hi: float):
    """``(program/op, ns inside the window)`` for each operation: the op
    named by its HLO instruction (the text before `` = ``) under the
    program whose execution contains it."""
    mods = sorted(rec["modules"], key=lambda m: m[1])
    j = 0
    for n, s, e in sorted(rec["ops"], key=lambda o: o[1]):
        c = min(e, hi) - max(s, lo)
        if c <= 0:
            continue
        while j < len(mods) and mods[j][2] < s:
            j += 1
        prog = (_SUFFIX.sub("", mods[j][0])
                if j < len(mods) and mods[j][1] <= s else "?")
        yield f"{prog}/{n.split(' = ')[0]}", c


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} .xplane.pb files under {path}")
    return found[0]


def reduce_dir(path: str, names: set) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(path)), names)

