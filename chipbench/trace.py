"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Device planes are ``/device:TPU:<n>``. On each, the ``XLA Ops`` line holds
one event per executed HLO op and the ``XLA Modules`` line one event per
executed program. Busy time is the union of the op intervals; idle gaps
are the holes in that union inside the traced window, the window being
the benchmark's own ``bench.traced`` host span. Each gap is named by what
the thread that drives the service (the one that holds ``bench.traced``)
was doing at its midpoint: the innermost ``bench.*`` span, and after a
``>`` the innermost other span inside it (a JAX dispatch, say), if any.

``cut`` writes a short stretch of a trace as a small ``.xplane.pb``, the
way the tests' fixture was made from a chip trace.
"""
from __future__ import annotations

import pathlib
import re
from collections import defaultdict

_DEVICE = re.compile(r"^/device:TPU:\d+$")
WINDOW_SPAN = "bench.traced"


def program_name(module_event: str) -> str:
    """``jit__decode(12)`` -> ``_decode``: the jitted function's name."""
    name = re.sub(r"\(\d+\)$", "", module_event)
    return name[4:] if name.startswith("jit_") else name


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_planes(planes) -> dict:
    """``planes``: objects with ``.name`` and ``.lines``, each line with
    ``.name`` and ``.events`` (``.name``, ``.start_ns``, ``.duration_ns``),
    as ``jax.profiler.ProfileData`` gives them. Returns seconds."""
    host_lines, devices = [], []
    for pl in planes:
        if _DEVICE.match(pl.name):
            devices.append(pl)
        elif pl.name.startswith("/host:"):
            host_lines.extend(pl.lines)

    window, driver = None, None
    for ln in host_lines:
        for ev in ln.events:
            if ev.name == WINDOW_SPAN:
                window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                driver = ln
    if window is None:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} host span")
    lo, hi = window

    busy_ns, programs, ops = 0.0, defaultdict(lambda: [0.0, 0]), \
        defaultdict(float)
    union = []
    for pl in devices:
        lines = {ln.name: ln for ln in pl.lines}
        for ev in getattr(lines.get("XLA Modules"), "events", ()):
            c = _clip([(ev.start_ns, ev.start_ns + ev.duration_ns)], lo, hi)
            if c:
                p = programs[program_name(ev.name)]
                p[0] += (c[0][1] - c[0][0]) / 1e9
                p[1] += 1
        spans = []
        evs = sorted(getattr(lines.get("XLA Ops"), "events", ()),
                     key=lambda ev: ev.start_ns)
        for i, ev in enumerate(evs):
            c = _clip([(ev.start_ns, ev.start_ns + ev.duration_ns)], lo, hi)
            if not c:
                continue
            spans.extend(c)
            end = ev.start_ns + ev.duration_ns
            if i + 1 < len(evs) and evs[i + 1].start_ns < end:
                continue          # a loop or call: its ops count instead
            ops[op_name(ev.name)] += (c[0][1] - c[0][0]) / 1e9
        merged = _merge(spans)
        busy_ns += sum(e - s for s, e in merged)
        if not union:
            union = merged

    gaps, prev = [], lo
    for s, e in union + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    host = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ev in (driver.events if driver is not None else ())
            if ev.name != WINDOW_SPAN]

    def during(t):
        inside = sorted((e - s, n) for s, e, n in host if s <= t < e)
        ours = [n for _, n in inside if n.startswith("bench.")]
        name = ours[0] if ours else "none"
        other = [n for _, n in inside if not n.startswith("bench.")]
        return f"{name}>{other[0]}" if other else name

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    n_dev = max(1, len(devices))
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n_dev / 1e9,
        "devices": len(devices),
        "programs": {k: {"seconds": v[0], "count": v[1]}
                     for k, v in programs.items()},
        "ops": dict(ops),
        "idle_gaps": [[during((s + e) / 2), (e - s) / 1e9]
                      for s, e in longest],
    }


def reduce_file(path) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(str(path)).planes)


def breakdown(red: dict) -> dict:
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": red["idle_gaps"][:10]}


def _quote(name: str) -> str:
    return name.replace("\\", "\\\\").replace('"', '\\"')


def cut(src, dst, start_s: float, length_s: float) -> None:
    """Write the stretch [start_s, start_s + length_s) (seconds from the
    start of the ``bench.traced`` span) of trace ``src`` to ``dst``: the
    driving thread's host spans and the first device's ``XLA Modules``
    and ``XLA Ops`` lines, with ``bench.traced`` cut to the stretch."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(str(src)).planes)
    win = driver = None
    for pl in planes:
        if pl.name.startswith("/host:"):
            for ln in pl.lines:
                for ev in ln.events:
                    if ev.name == WINDOW_SPAN:
                        win, driver = ev.start_ns, ln
    lo = win + start_s * 1e9
    hi = lo + length_s * 1e9
    keep = [("/host:CPU", [(driver.name, driver.events)])]
    dev = sorted((pl for pl in planes if _DEVICE.match(pl.name)),
                 key=lambda pl: pl.name)[0]
    keep.append((dev.name, [(ln.name, ln.events) for ln in dev.lines
                            if ln.name in ("XLA Modules", "XLA Ops")]))
    out = []
    for pid, (pname, lines) in enumerate(keep, 1):
        names: dict = {}
        body = []
        for lid, (lname, events) in enumerate(lines, 1):
            evs = []
            for ev in events:
                s0, e0 = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name == WINDOW_SPAN:
                    s0, e0 = lo, hi
                elif e0 <= lo or s0 >= hi:
                    continue
                mid = names.setdefault(ev.name, len(names) + 1)
                evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                           f"{int(round(s0 * 1000))} duration_ps: "
                           f"{int(round((e0 - s0) * 1000))} }}")
            body.append(f'lines {{ id: {lid} name: "{_quote(lname)}" '
                        f'timestamp_ns: 0 {" ".join(evs)} }}')
        meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{_quote(n)}" }} }}'
                        for n, i in names.items())
        out.append(f'planes {{ id: {pid} name: "{_quote(pname)}" '
                   f'{" ".join(body)} {meta} }}')
    data = ProfileData.text_proto_to_serialized_xspace("\n".join(out))
    pathlib.Path(dst).write_bytes(data)
