"""The host layers of a cell's runs: what the per-layer readers that take
the service's registry and its program spans read.

    python3 chipbench/layers.py --workload <cell> --seeds 1,2 --seconds 20
        [--trace 0|1] [--keep-trace DIR]

Each run builds and drives the cell as ``chipbench/run.py`` does, with
the stretch of the window traced unless ``--trace 0``; its record holds
``registry`` (the window's deltas of the service registry's counters and
``span.<path>.seconds`` sums and counts) and ``spans`` (the device's
idle time split by program span, ``chipbench/spans.py``), as a run of
``run.py`` does. It skips the check against the reference: ``run.py``
decides ``correct``. Each run prints the per-span idle table on standard
error and one JSON line on standard output: the readers' values under
``metrics``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parent.parent)]

READERS = ("tokens_per_s", "transfer_mb_per_step", "coder_host_ms", "scheduler_host_ms",
           "idle_transfer_ms", "idle_coder_ms", "idle_scheduler_ms",
           "step_compiles", "step_ms", "device_idle_share",
           "decode_device_ms", "cdf_device_ms")


def run(cell_name: str, seed: int, seconds: float, t_start: float,
        trace_on: bool = True, keep_trace=None, require_tpu: bool = True,
        cell=None) -> dict:
    from chipbench import harness
    cell = cell or harness.load_cell(cell_name)
    dev = harness.accelerator(cell["cell"]["chips"], require_tpu)[0]
    harness.compile_cache()
    b = harness.Bench(cell, seed, harness.CompileClock.get(), t_start)
    tdir = tempfile.mkdtemp(prefix="chipbench_layers_") if trace_on else None
    rec = harness.drive(b, seconds, trace_dir=tdir)
    b.free()
    rec["trace"], rec["spans"] = (harness.read_trace(tdir, keep_trace)
                                  if tdir else (None, None))
    if rec["spans"]:
        harness.log_idle_split(rec["spans"])
    if rec["trace"]:
        harness.log(f"longest idle gaps: {rec['trace']['idle_gaps']}")
    metrics = {}
    for name in READERS:
        v = harness.metric_reader(name, cell["bench_dir"])(rec)
        if v is not None:
            metrics[name] = float(v)
    return {"workload": cell_name, "seed": seed, "metrics": metrics,
            "device": {"platform": dev.platform, "kind": dev.device_kind},
            "counters": rec["counters"], "registry": rec["registry"],
            "spans": rec["spans"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run(args.workload, seed, args.seconds, t_start,
                  trace_on=bool(args.trace), keep_trace=args.keep_trace)
        print(json.dumps(out), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
