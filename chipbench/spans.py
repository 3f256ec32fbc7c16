"""Reduction of a profiler trace (``.xplane.pb``) to the program's own
spans: where the device's idle time went on the host.

The program mirrors its ``obs`` spans into the profiler, so the thread
that drives the service (the one that holds ``bench.traced``) carries
one host event per span: ``service.step``, ``service.refill``,
``service.finish_slot``, ``model.decode_step``, ``cdf.build``,
``transfer.cdf_to_host``, ``coder.step``, ``rans.flush_slot`` and the
rest of the ``PREFIXES`` families. Each idle
interval of the first device's busy union inside ``bench.traced`` is cut
at the edges of those spans, and each piece goes to the innermost
program span open over it, or to ``none`` outside every one (the client
between polls). The split is a partition: every idle nanosecond lands
in exactly one span, and in one of the three ``BUCKETS``.

A program without these spans gives an empty split and no
``service.step``, and one that opens ``service.step`` on only some of
its model steps (an older one timed 1 step in 16) has fewer of them than
``model.decode_step`` spans: the readers of these numbers then return
None.
"""
from __future__ import annotations

from collections import defaultdict

from chipbench.trace import _DEVICE, WINDOW_SPAN, _clip, _merge

PREFIXES = ("service.", "model.", "transfer.", "cdf.", "coder.", "rans.")
OUTSIDE = "none"


def bucket(span: str) -> str:
    """The layer an idle piece is charged to: ``transfer`` (the CDF
    program and the trip of its ids and CDFs to the host), ``coder`` (the host
    rANS coder) or ``scheduler`` (all else: the step's own Python,
    refills, dispatch, and the client between polls)."""
    if span.startswith(("transfer.", "cdf.")):
        return "transfer"
    if span.startswith(("coder.", "rans.")):
        return "coder"
    return "scheduler"


BUCKETS = ("transfer", "coder", "scheduler")


def innermost(events, lo: int, hi: int) -> list:
    """Segments ``(start, end, name)`` covering [lo, hi) in order, each
    named by the innermost of ``events`` (``(start, end, name)``, nested
    as one thread's spans are) open over it, else ``OUTSIDE``."""
    out, stack, t = [], [], lo

    def emit(end, name):
        nonlocal t
        if end > t:
            out.append((t, end, name))
            t = end

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            emit(end, top)
        emit(s, stack[-1][1] if stack else OUTSIDE)
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    while stack:
        end, top = stack.pop()
        emit(end, top)
    emit(hi, OUTSIDE)
    return out


def split(gaps, segments) -> dict:
    """Seconds of the (sorted, disjoint) ``gaps`` under each segment's
    name; ``segments`` cover the gaps, in order."""
    out, j = defaultdict(float), 0
    for a, b in gaps:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s, e, name = segments[k]
            out[name] += (min(b, e) - max(a, s)) / 1e9
            k += 1
    return dict(out)


def reduce_planes(planes) -> dict:
    """``planes`` as ``chipbench.trace.reduce_planes`` takes them.
    Returns seconds: the traced window, its idle total, the idle split by
    innermost program span (``idle_by_span``) and by bucket (``idle``),
    and the numbers of ``service.step`` and ``model.decode_step`` spans
    that start in the window."""
    window = driver = None
    device = None
    for pl in planes:
        if _DEVICE.match(pl.name):
            device = device or pl
        elif pl.name.startswith("/host:"):
            for ln in pl.lines:
                for ev in ln.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                        driver = ln
    if window is None:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} host span")
    lo, hi = window
    ops = {ln.name: ln for ln in (device.lines if device else ())}.get(
        "XLA Ops")
    busy = _merge(_clip([(ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in getattr(ops, "events", ())], lo, hi))
    gaps, prev = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
             for ev in driver.events if ev.name.startswith(PREFIXES)]
    by_span = split(gaps, innermost(spans, lo, hi))
    idle = dict.fromkeys(BUCKETS, 0.0)
    for name, s in by_span.items():
        idle[bucket(name)] += s
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_s": sum((b - a) for a, b in gaps) / 1e9,
        "idle": idle,
        "idle_by_span": by_span,
        "steps": _starts(spans, "service.step", lo, hi),
        "decode_steps": _starts(spans, "model.decode_step", lo, hi),
    }


def _starts(spans, name, lo, hi) -> int:
    return sum(1 for s, _, n in spans if n == name and lo <= s < hi)


def reduce_file(path) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(str(path)).planes)


def table(red: dict) -> str:
    """The idle split as lines: span, bucket, ms per step, share of idle."""
    steps = max(red["steps"], 1)
    idle = red["idle_s"] or 1.0
    rows = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])
    return "\n".join(
        f"{name:28s} {bucket(name):9s} {1e3 * s / steps:9.4f} ms/step "
        f"{100 * s / idle:6.2f} %" for name, s in rows)


# ------------------------------------------------ for the metric readers
def span_seconds(rec, leaves):
    """Window seconds of the service's spans (``rec["registry"]``) whose
    last path segment is one of ``leaves``; None when it has none."""
    spans = (rec.get("registry") or {}).get("spans", {})
    hit = [v["seconds"] for k, v in spans.items()
           if k.rsplit("/", 1)[-1] in leaves]
    return sum(hit) if hit else None


def idle_ms(rec, name):
    """Device idle milliseconds per ``service.step`` of the traced
    stretch charged to bucket ``name`` (``rec["spans"]``, from
    ``reduce_planes``); None without a ``service.step`` on every model
    step of the stretch (one may straddle each of its edges)."""
    s = rec.get("spans")
    if not s or not s["steps"] or abs(s["steps"] - s["decode_steps"]) > 1:
        return None
    return 1e3 * s["idle"][name] / s["steps"]
