"""Aggregate stored per-cell roofline JSONs (``results/roofline/``:
arch, shape, roofline terms, bytes per device) into the §Roofline table.
Nothing in the repo writes those records any more.

With ``--measured FILE`` (a JSON object mapping ``"arch/shape"`` to a
measured per-step wall time in seconds) the summary additionally emits
the **attainment** column — ``t_star / measured``, the fraction of the
binding compute/memory/collective bound each config actually achieves
(DESIGN.md §13; the ROADMAP's "as fast as the hardware allows" signal).

  PYTHONPATH=src python -m benchmarks.roofline [--mesh single] [--md]
  PYTHONPATH=src python -m benchmarks.roofline --measured steps.json
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path[:0] = ["src", "."]

from repro.obs import console  # noqa: E402

RESULTS = pathlib.Path(__file__).resolve().parents[1] / "results" / "roofline"

ARCH_ORDER = ["llava_next_34b", "mamba2_130m", "qwen3_moe_235b_a22b",
              "granite_moe_1b_a400m", "qwen3_14b", "deepseek_7b",
              "h2o_danube_3_4b", "qwen3_1_7b", "zamba2_7b",
              "whisper_large_v3"]
SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def load(mesh: str, tag: str = "") -> dict:
    out = {}
    suffix = f"__{mesh}" + (f"__{tag}" if tag else "")
    for p in sorted(RESULTS.glob(f"*{suffix}.json")):
        d = json.loads(p.read_text())
        out[(d["arch"], d["shape"])] = d
    return out


def _render(hdr: list, rows: list, md: bool) -> str:
    widths = [max(len(h), *(len(row[i]) for row in rows)) if rows else len(h)
              for i, h in enumerate(hdr)]
    sep = " | " if md else "  "
    if md:
        lines = ["| " + sep.join(h.ljust(w)
                                 for h, w in zip(hdr, widths)) + " |",
                 "|" + "|".join("-" * (w + 2) for w in widths) + "|"]
        for row in rows:
            lines.append("| " + sep.join(c.ljust(w)
                                         for c, w in zip(row, widths)) + " |")
    else:
        lines = [sep.join(h.ljust(w) for h, w in zip(hdr, widths))]
        for row in rows:
            lines.append(sep.join(c.ljust(w) for c, w in zip(row, widths)))
    return "\n".join(lines)


def fmt_table(cells: dict, md=False) -> str:
    hdr = ["arch", "shape", "t_comp(s)", "t_mem(s)", "t_coll(s)",
           "bottleneck", "useful", "roofline", "mem/dev(GiB)"]
    rows = []
    for a in ARCH_ORDER:
        for s in SHAPE_ORDER:
            d = cells.get((a, s))
            if d is None:
                continue
            if "skipped" in d:
                rows.append([a, s, "-", "-", "-", "SKIP", "-", "-", "-"])
                continue
            r = d["roofline"]
            mem = d["memory"]["bytes_per_device"] / 2**30
            rows.append([
                a, s, f"{r['t_compute_s']:.3f}", f"{r['t_memory_s']:.3f}",
                f"{r['t_collective_s']:.3f}", r["bottleneck"],
                f"{r['useful_flops_ratio']:.2f}",
                f"{r['roofline_fraction']:.3f}", f"{mem:.2f}"])
    return _render(hdr, rows, md)


def cell_t_star(r: dict) -> float:
    """Binding roofline bound for a stored cell's roofline dict —
    recorded directly by newer cells, derived for pre-§13 artifacts."""
    if "t_star_s" in r:
        return float(r["t_star_s"])
    return max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])


def attainment_rows(cells: dict, measured: dict) -> list:
    """(arch, shape, t_star, measured_s, attainment, bottleneck) per
    cell that has a measured step time. ``measured`` maps
    ``"arch/shape"`` -> wall seconds per step."""
    out = []
    for a in ARCH_ORDER:
        for s in SHAPE_ORDER:
            d = cells.get((a, s))
            if d is None or "skipped" in d:
                continue
            m = measured.get(f"{a}/{s}")
            if m is None or not m > 0:
                continue
            r = d["roofline"]
            t_star = cell_t_star(r)
            out.append((a, s, t_star, float(m),
                        t_star / float(m) if t_star else 0.0,
                        r["bottleneck"]))
    return out


def attainment_table(cells: dict, measured: dict, md=False) -> str:
    hdr = ["arch", "shape", "t_star(s)", "measured(s)", "attainment",
           "bottleneck"]
    rows = [[a, s, f"{t:.4f}", f"{m:.4f}", f"{att:.3f}", bn]
            for a, s, t, m, att, bn in attainment_rows(cells, measured)]
    return _render(hdr, rows, md)


def summarize(mesh="single", md=False, tag="", measured=None):
    cells = load(mesh, tag)
    console(fmt_table(cells, md=md))
    ok = [d for d in cells.values() if "skipped" not in d]
    if not ok:
        return
    worst = min(ok, key=lambda d: d["roofline"]["roofline_fraction"])
    coll = max(ok, key=lambda d: d["roofline"]["t_collective_s"] /
               max(1e-12, max(d["roofline"]["t_compute_s"],
                              d["roofline"]["t_memory_s"])))
    console(f"\ncells: {len(cells)} ({len(ok)} compiled, "
            f"{len(cells)-len(ok)} skipped)")
    console(f"worst roofline fraction: {worst['arch']}/{worst['shape']} "
            f"({worst['roofline']['roofline_fraction']:.4f})")
    console(f"most collective-bound: {coll['arch']}/{coll['shape']}")
    if measured:
        rows = attainment_rows(cells, measured)
        console("\nmeasured vs roofline:")
        console(attainment_table(cells, measured, md=md))
        if rows:
            best = max(rows, key=lambda r: r[4])
            worst_a = min(rows, key=lambda r: r[4])
            console(f"attainment: best {best[0]}/{best[1]} ({best[4]:.3f}), "
                    f"worst {worst_a[0]}/{worst_a[1]} ({worst_a[4]:.3f})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--md", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--measured", default="",
                    help="JSON file: {'arch/shape': step_seconds} -> "
                         "adds the attainment table")
    args = ap.parse_args()
    measured = None
    if args.measured:
        measured = json.loads(pathlib.Path(args.measured).read_text())
    summarize(args.mesh, args.md, args.tag, measured=measured)


if __name__ == "__main__":
    main()
