"""Several runs of one cell in one process, for the studies that set the
benchmark's numbers; the benchmark's own runs do not use it.

    python3 chipbench/study.py --workload <cell> --seeds 1,2,3 --seconds 20
        [--control] [--rates 3,4,5] [--backlog-every 1] [--trace 1 --keep-trace DIR]

``--control`` also reads the control (the reference computed in int8)
on each run's sampled chunks: the readings that a limit of ``correct``
is set from. ``--rates`` overrides an open loop's offered rate, one run
per rate and seed, for the sweep that finds the read-back knee. Every
run prints one JSON line on standard output.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parent.parent)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", default="")
    ap.add_argument("--backlog-every", type=float, default=0.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    from chipbench import harness
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    t_start = T_START
    for rate in rates:
        for seed in (int(s) for s in args.seeds.split(",")):
            out = harness.run(args.workload, seed, args.seconds,
                              bool(args.trace), t_start=t_start, rate=rate,
                              backlog_every=args.backlog_every,
                              control=args.control,
                              keep_trace=args.keep_trace)
            print(json.dumps(dict(out, seed=seed, rate=rate)), flush=True)
            t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
