"""The benchmark's harness: one cell, one seed, one run.

A cell names a configuration and a traffic mix; everything about them
is read from files found by those names:

    chipbench/configs/<config>.json     sizes, service, pool, weight init
    chipbench/reference/<name>.py       the plain reference the config
                                        names (``"reference"``, default
                                        ``dense``)
    chipbench/traffic/<mix>.json        the generator's parameters
    chipbench/limits/<cell>.json        the limits ``correct`` is held to
    chipbench/metrics/<metric>.py       one reader per metric

A run builds the configuration's weights (the reference's
``make_weights``, from the file's fixed ``init.seed``) on the device,
has the model write a pool of documents from BOS under the run's seed,
builds ``CompressionService`` at the configuration's slots, chunk and
top-K, warms the cell's shapes, then drives ``submit_compress`` /
``submit_decompress`` and ``poll()`` for the window as a user does
(``drive``), and checks what came back against the plain reference
(``run``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "chipbench"
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ files
def _json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, root: pathlib.Path = ROOT,
              bench_file: pathlib.Path | None = None,
              limits_dir: pathlib.Path | None = None) -> dict:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files and
    metrics, each found by name under ``<root>/chipbench``."""
    bench_dir = root / "chipbench"
    bench = _json(bench_file or root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} (known: {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if name in m.get(
        "workloads", [name] if m["moves"] in reported else [])]
    return {
        "name": name, "cell": cell, "bench_dir": bench_dir,
        "config": _json(root / configs[cell["config"]]["file"]),
        "traffic": _json(bench_dir / "traffic" / f"{cell['traffic']}.json"),
        "limits": _json((limits_dir or bench_dir / "limits")
                        / f"{name}.json"),
        "end_to_end": e2e, "per_layer": per_layer,
    }


def metric_reader(name: str, bench_dir: pathlib.Path = BENCH):
    """``read(rec)`` of ``metrics/<name>.py``; a copy of a metric named
    ``<base>.<cells>`` is read by ``metrics/<base>.py`` unless it has a
    file of its own."""
    for stem in (name, name.split(".")[0]):
        path = bench_dir / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"chipbench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r}")


def peaks_for(kind: str) -> dict:
    table = _json(BENCH / "peaks.json")
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} "
                         f"(known: {sorted(table)})")
    return table[kind]


# ------------------------------------------------------------- the device
def accelerator(chips: int, require_tpu: bool = True):
    """The devices of this run; exits non-zero (before anything is
    measured) unless JAX's first device is a TPU and there are enough."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        log(f"chipbench: JAX found {devs[0].platform!r}, not a TPU; "
            f"nothing is measured off the chip")
        raise SystemExit(3)
    if len(devs) < chips:
        log(f"chipbench: the cell needs {chips} chips, JAX found "
            f"{len(devs)}")
        raise SystemExit(3)
    return devs


def compile_cache() -> str:
    """JAX's persistent compilation cache where the program's entry points
    keep it (a set ``JAX_COMPILATION_CACHE_DIR`` wins, else
    ``<checkout>/.jax_cache``), taking every program, however quick its
    compile, so that only a cell's first run in a checkout compiles."""
    import jax
    from repro.compile_cache import configure_compile_cache
    path = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading
    from the persistent cache), and the number of backend compiles; one
    per process (``get``), its listeners registered once."""

    _one = None

    @classmethod
    def get(cls) -> "CompileClock":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event in self.EVENTS:
                self.seconds += duration
            if event == self.EVENTS[2]:
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


# ------------------------------------------------------------ the program
def reference_module(conf: dict, bench_dir: pathlib.Path = BENCH):
    """The plain reference a configuration file names under
    ``"reference"`` (``dense`` where it names none): the module
    ``<bench_dir>/reference/<name>.py``, imported once as
    ``chipbench.reference.<name>``, the name the readers find it by."""
    name = conf.get("reference", "dense")
    full = f"chipbench.reference.{name}"
    if full not in sys.modules and bench_dir != BENCH:
        spec = importlib.util.spec_from_file_location(
            full, bench_dir / "reference" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[full] = mod
    return importlib.import_module(full)


def program_config(conf: dict, bench_dir: pathlib.Path = BENCH):
    """The program's ModelConfig for a configuration file: its module's
    CONFIG with every ``model`` number the file holds set on it, by the
    ``PROGRAM_FIELDS`` of the reference it names; ``program.fields``
    wins."""
    mod = importlib.import_module(conf["program"]["module"])
    fields = reference_module(conf, bench_dir).PROGRAM_FIELDS
    kw = {f: conf["model"][k] for k, f in fields.items()
          if k in conf["model"]}
    kw.update(conf["program"].get("fields", {}))
    cfg = mod.CONFIG.with_(**kw)
    if (cfg.padded_heads, cfg.padded_kv_heads) != (cfg.n_heads,
                                                   cfg.n_kv_heads):
        raise SystemExit("head padding would change the model the "
                         "reference computes")
    return cfg


def model_spec(conf: dict, cfg) -> dict:
    """The config file's ``model`` numbers plus the program's vocabulary
    padding, which fixes the stored embedding's rows."""
    return dict(conf["model"], vocab_pad_multiple=cfg.vocab_pad_multiple)


def check_layout(params, cfg) -> None:
    """The tree handed to the service (the reference's ``make_weights``)
    has the program's schema shapes."""
    import jax
    from repro.models.schema import abstract_params
    want = jax.tree_util.tree_map(lambda a: a.shape, abstract_params(cfg))
    have = jax.tree_util.tree_map(lambda a: a.shape, params)
    if want != have:
        raise SystemExit(f"parameter layout differs from the program's "
                         f"schema: {want} != {have}")


def seeds(seed: int) -> dict:
    """Sub-seeds of a run from its ``seed`` (any non-negative integer): the
    document pool, the order of the traffic, and the sample that is
    checked. The weights are the configuration's (``init["seed"]``): one
    model per configuration, as a deployment serves one."""
    p, t, c = np.random.SeedSequence(int(seed)).generate_state(3)
    return {"pool": int(p) & 0x7FFFFFFF, "traffic": int(t) & 0x7FFFFFFF,
            "check": int(c) & 0x7FFFFFFF}


class Bench:
    """A built cell: weights, pool, service, warmed."""

    def __init__(self, cell: dict, seed: int, clock: CompileClock,
                 t_start: float):
        import jax
        from repro.serve.engine import ModelPredictor
        from repro.service import CompressionService

        self.cell, self.seed = cell, seed
        self.sub = seeds(seed)
        conf, mix = cell["config"], cell["traffic"]
        self.conf, self.mix = conf, mix
        self.ref = ref = reference_module(conf, cell["bench_dir"])
        self.cfg = program_config(conf, cell["bench_dir"])
        self.m = model_spec(conf, self.cfg)
        svc, pool = conf["service"], conf["pool"]
        self.split = {"tpu_init_s": time.perf_counter() - t_start}
        compile0, hits0 = clock.seconds, clock.cache_hits

        t = time.perf_counter()
        params = ref.make_weights(self.m, conf["init"],
                                  conf["init"]["seed"])
        jax.block_until_ready(params)
        check_layout(params, self.cfg)
        self.split["weights_s"] = time.perf_counter() - t

        t = time.perf_counter()
        self.bos = self.cfg.vocab_size - 1
        self.pool = ref.sample_documents(
            self.m, params, n_docs=pool["documents"], batch=pool["batch"],
            n_tokens=svc["chunk_size"], top_k=pool["sample_top_k"],
            bos=self.bos, seed=self.sub["pool"])
        self.split["pool_s"] = time.perf_counter() - t

        t = time.perf_counter()
        self.pred = ModelPredictor(params, self.cfg, bos_id=self.bos)
        self.svc = CompressionService(
            self.pred, slots=svc["slots"], chunk_size=svc["chunk_size"],
            topk=svc["topk"], precision=svc["precision"])
        self.rng = np.random.default_rng(self.sub["traffic"])
        from chipbench import traffic
        if mix["direction"] == "compress":
            self.svc.submit_compress(self.pool[0, :16]).result()
            self.split["warmup_s"] = time.perf_counter() - t
        else:
            sizes = traffic.deck_sizes(mix["lengths"])
            self.replay = [traffic.job_tokens(self.pool, int(n), self.rng)
                           for n in sizes]
            hs = [self.svc.submit_compress(x) for x in self.replay]
            self.blobs = [h.result()[0] for h in hs]
            self.split["replay_compress_s"] = time.perf_counter() - t
            t = time.perf_counter()
            small = int(np.argmin(sizes))
            self.svc.submit_decompress(self.blobs[small]).result()
            self.split["warmup_s"] = time.perf_counter() - t
        self.split["compile_s"] = clock.seconds - compile0
        self.split["compile_cache_hits"] = clock.cache_hits - hits0
        self.params = params

    def fresh_service(self) -> None:
        """Replace the service by a new one over the same predictor (the
        same compiled programs), dropping the work a closed loop left in
        flight at the window's close."""
        from repro.service import CompressionService
        svc = self.conf["service"]
        del self.svc
        gc.collect()
        self.svc = CompressionService(
            self.pred, slots=svc["slots"], chunk_size=svc["chunk_size"],
            topk=svc["topk"], precision=svc["precision"])

    def counters(self) -> dict:
        st = self.svc.stats
        return {k: int(getattr(st, k)) for k in
                ("model_steps", "lane_steps", "token_steps", "escapes",
                 "prefill_steps")}

    def free(self) -> None:
        """Drop the service, the predictor and the weights."""
        del self.svc, self.pred, self.params
        gc.collect()


def registry_values(reg) -> dict:
    """Counters and span-histogram sums/counts of a service registry, by
    name (spans by path)."""
    out = {"counters": {}, "spans": {}}
    for name, m in reg.snapshot().items():
        if m["type"] == "counter":
            out["counters"][name] = m["value"]
        elif name.startswith("span.") and name.endswith(".seconds"):
            out["spans"][name[5:-8]] = {"seconds": m["sum"],
                                        "count": m["count"]}
    return out


def registry_delta(after: dict, before: dict) -> dict:
    zero = {"seconds": 0.0, "count": 0}
    return {
        "counters": {k: v - before["counters"].get(k, 0)
                     for k, v in after["counters"].items()},
        "spans": {k: {f: v[f] - before["spans"].get(k, zero)[f]
                      for f in zero}
                  for k, v in after["spans"].items()},
    }


# --------------------------------------------------------------- the window
class _Tracer:
    """Profiles the stretch [start, end) of the window (seconds from its
    start) under a ``bench.traced`` host span."""

    def __init__(self, start: float, end: float, out_dir: str | None):
        self.start, self.end, self.dir = start, end, out_dir
        self.on = self.done = False

    def tick(self, now: float) -> None:
        import jax
        if self.dir is None or self.done:
            return
        if not self.on and now >= self.start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # no event per Python call
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            from chipbench.trace import WINDOW_SPAN
            self.span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self.span.__enter__()
            self.on = True
        elif self.on and now >= self.end:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.on and not self.done:
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.done = True


def drive(b: Bench, seconds: float, *, trace_dir: str | None = None,
          rate: float | None = None, backlog_every: float = 0.0) -> dict:
    """Drive the cell's traffic for ``seconds`` and return the run's
    record; job times are seconds from the window's start.

    A closed loop keeps enough jobs outstanding to hold
    ``outstanding_chunks_per_slot`` chunks per slot, and stops at the
    window's close: what it finished in the window is its work. An open
    loop submits each job when it is due, and after the close drains the
    jobs due in the window up to ``drain_cap_s``. The scheduler's
    counters, the service registry's counters and span sums
    (``registry``) and the seconds spent inside ``poll()`` are read at
    the window's close. Each finished job records its container's
    ``bytes``."""
    import jax
    from chipbench import traffic
    mix, svc = b.mix, b.svc
    C = b.conf["service"]["chunk_size"]
    slots = b.conf["service"]["slots"]
    tracer = _Tracer(*_trace_span(seconds), trace_dir)
    span = jax.profiler.TraceAnnotation
    jobs, live = [], []
    poll_s, backlog, next_b = 0.0, [], 0.0
    closed = mix["loop"] == "closed"
    if closed:
        sizes = traffic.size_stream(mix["lengths"], b.rng)
        target = mix["outstanding_chunks_per_slot"] * slots
        cap = math.inf
    else:
        mix = dict(mix, rate_jobs_per_s=rate or mix["rate_jobs_per_s"])
        due = traffic.arrival_times(mix, seconds, b.rng)
        order = traffic.replay_order(len(b.blobs), len(due), b.rng)
        nxt = 0
        cap = seconds + mix["drain_cap_s"]
    window = None
    c0, r0 = b.counters(), registry_values(svc.registry)
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        tracer.tick(now)
        if window is None and now >= seconds:
            window = {"t_close": now, "poll_s": poll_s,
                      "counters": _delta(b.counters(), c0),
                      "registry": registry_delta(
                          registry_values(svc.registry), r0)}
            if closed:
                break
        if closed:
            with span("bench.submit"):
                while sum(-(-j["n"] // C) for j in live) < target:
                    n = int(next(sizes))
                    j = {"n": n, "due": now,
                         "tokens": traffic.job_tokens(b.pool, n, b.rng)}
                    j["h"] = svc.submit_compress(j["tokens"])
                    jobs.append(j)
                    live.append(j)
        else:
            with span("bench.submit"):
                while nxt < len(due) and due[nxt] <= now:
                    k = int(order[nxt])
                    j = {"n": len(b.replay[k]), "due": float(due[nxt]),
                         "replay": k, "t_submit": now}
                    j["h"] = svc.submit_decompress(b.blobs[k])
                    jobs.append(j)
                    live.append(j)
                    nxt += 1
            if not live:
                if nxt >= len(due):
                    if window is not None:
                        break
                    time.sleep(max(0.0, min(0.002, seconds - now)))
                else:
                    time.sleep(max(0.0, min(0.002, due[nxt] - now)))
                continue
        if backlog_every and now >= next_b:
            backlog.append((round(now, 3), len(live)))
            next_b += backlog_every
        t = time.perf_counter()
        with span("bench.poll" if window is None else "bench.drain"):
            svc.poll()
        poll_s += time.perf_counter() - t
        done_at = time.perf_counter() - t0
        still = []
        for j in live:
            if j["h"].done():
                j["t_done"] = done_at
            else:
                still.append(j)
        live = still
        if done_at > cap:
            break
    tracer.stop()
    end = time.perf_counter() - t0
    for j in jobs:
        h = j.pop("h")
        if "t_done" not in j:
            continue
        try:
            res = h.result()
            if closed:
                j["blob"] = res[0]
                j["bytes"] = len(res[0])
            else:
                j["tokens_out"] = res
                j["bytes"] = len(b.blobs[j["replay"]])
            j["chunks"] = {d.chunk_index: d.coded_bits
                           for d in h.diagnostics.chunks}
        except Exception as e:                        # noqa: BLE001
            j["error"] = f"{type(e).__name__}: {e}"
    if closed:              # jobs still in flight at the close were not due
        jobs = [j for j in jobs if "t_done" in j]
    return {
        "kind": "compress" if closed else "decompress",
        "seconds": seconds, "jobs": jobs, **window,
        "backlog": backlog,
        "lateness_s": [j["t_submit"] - j["due"] for j in jobs
                       if "t_submit" in j],
        "drain_s": max(0.0, end - window["t_close"]),
        "cap_s": cap,
    }


def _delta(c1: dict, c0: dict) -> dict:
    return {k: c1[k] - c0[k] for k in c0}


def _trace_span(seconds: float):
    """The traced stretch: 5 s from a quarter into the window."""
    start = 0.25 * seconds
    return start, min(seconds, start + 5.0)


def mean_position(rec: dict, C: int) -> float:
    """Mean position (from 0) of the tokens the run's jobs code: every
    chunk codes each of its positions once."""
    tot = pos = 0
    for j in rec["jobs"]:
        full, rest = divmod(j["n"], C)
        for v in [C] * full + ([rest] if rest else []):
            tot += v
            pos += v * (v - 1) / 2
    return pos / tot if tot else 0.0


# ------------------------------------------------------------------- check
def sample_jobs(rec: dict, seed: int, tokens: int) -> list:
    """Finished jobs drawn by the seed: the longest, then others in a
    seeded order until ``tokens`` tokens are in the sample."""
    ok = [j for j in rec["jobs"] if "t_done" in j and "error" not in j]
    if not ok:
        return []
    rng = np.random.default_rng(seed)
    first = max(range(len(ok)), key=lambda i: ok[i]["n"])
    pick, total = [ok[first]], ok[first]["n"]
    for i in rng.permutation(len(ok)):
        if total >= tokens:
            break
        if i != first:
            pick.append(ok[i])
            total += ok[i]["n"]
    return pick


def round_trip(b: Bench, rec: dict, sample: list) -> int:
    """Tokens that do not come back as they went in: for a compress run,
    the sampled containers decompressed by a fresh service at the same
    slots over the same predictor; for a decompress run, every finished
    job against its stored tokens."""
    bad = 0
    if rec["kind"] == "compress":
        b.fresh_service()
        hs = [(j, b.svc.submit_decompress(j["blob"])) for j in sample]
        for j, h in hs:
            try:
                bad += _mismatch(h.result(), j["tokens"])
            except Exception:                          # noqa: BLE001
                bad += j["n"]
    else:
        for j in rec["jobs"]:
            if "tokens_out" in j:
                bad += _mismatch(j["tokens_out"], b.replay[j["replay"]])
    return bad


def _mismatch(out, ref) -> int:
    out, ref = np.asarray(out), np.asarray(ref)
    if out.shape != ref.shape:
        return max(len(ref), 1)
    return int((out != ref).sum())


def chunk_table(sample: list, tokens_of, C: int):
    """The sampled jobs cut into chunks as the service codes them: tokens
    (n, C) zero-padded, valid lengths (n,), and the program's coded bits
    per chunk (NaN where the run reported none)."""
    chunks, valid, prog = [], [], []
    for j in sample:
        x = tokens_of(j)
        for c in range(-(-len(x) // C)):
            part = x[c * C:(c + 1) * C]
            row = np.zeros(C, np.int32)
            row[:len(part)] = part
            chunks.append(row)
            valid.append(len(part))
            prog.append(j["chunks"].get(c, math.nan))
    if not chunks:
        return (np.zeros((0, C), np.int32), np.zeros(0, np.int64),
                np.zeros(0))
    return (np.stack(chunks), np.asarray(valid, np.int64),
            np.asarray(prog, np.float64))


def reference_bits(ref, conf: dict, m: dict, chunks, valid, block: int,
                   int8: bool = False) -> np.ndarray:
    """Code length of each chunk under the reference module ``ref``, which
    makes its own weights from the configuration's seed; with ``int8`` it
    is the control."""
    import jax
    from chipbench.reference import codelen
    svc = conf["service"]
    params = ref.make_weights(m, conf["init"], conf["init"]["seed"])
    bits = codelen.chunk_bits(ref, m, params, chunks, valid, k=svc["topk"],
                              precision=svc["precision"],
                              bos=m["vocab_size"] - 1, block=block,
                              int8=int8)
    del params
    jax.clear_caches()
    return bits


def read_trace(tdir: str, keep: str | None = None):
    """The device's and the program spans' reductions of the profiler
    trace written under ``tdir`` (``chipbench/trace.py``,
    ``chipbench/spans.py``), or (None, None) where none was written;
    ``tdir`` is removed, the trace copied to ``keep`` first."""
    from chipbench import spans, trace as tr
    files = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))
    red = sp = None
    if files:
        if keep:
            pathlib.Path(keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(files[-1], keep)
        red, sp = tr.reduce_file(files[-1]), spans.reduce_file(files[-1])
    shutil.rmtree(tdir, ignore_errors=True)
    return red, sp


def log_idle_split(sp: dict) -> None:
    from chipbench import spans
    log(f"idle by program span ({sp['steps']} steps, {sp['idle_s']!r} s "
        f"idle of {sp['window_s']!r} s):\n" + spans.table(sp))


def gaps(bits, ref, valid) -> dict:
    """What ``correct`` compares between a side's per-chunk code lengths
    and the reference's: ``excess_bits_per_token``, the bits per token
    the side paid beyond the reference over the sample, and
    ``abs_gap_bits_per_token``, the chunks' absolute gaps summed over the
    sample's tokens. NaN (a chunk with no reading) fails every limit."""
    n = float(valid.sum())
    if not n:
        return {"excess_bits_per_token": math.nan,
                "abs_gap_bits_per_token": math.nan}
    d = np.asarray(bits, np.float64) - ref
    return {"excess_bits_per_token": float(d.sum() / n),
            "abs_gap_bits_per_token": float(np.abs(d).sum() / n)}


def _finite(v):
    return v if v is not None and math.isfinite(v) else None


def judge(readings: dict, limits: dict) -> tuple[dict, bool]:
    """Each reading beside its limit, and whether every one is within it;
    a reading that is missing or not finite fails. The program and the
    control are judged by this one rule."""
    compared = {k: {"value": _finite(v), "limit": limits[k]}
                for k, v in readings.items()}
    return compared, all(c["value"] is not None and c["value"] <= c["limit"]
                         for c in compared.values())


# --------------------------------------------------------------------- run
def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, cell: dict | None = None,
        keep_trace: str | None = None, rate: float | None = None,
        backlog_every: float = 0.0, control: bool = False) -> dict:
    """One run of one cell; returns the result line's object. With
    ``control`` it also puts the control (the reference in int8) in the
    program's place on the same chunks and judges it by the same limits
    and rule: its readings and verdict come under ``control`` and
    ``control_correct``."""
    cell = cell or load_cell(cell_name)
    devs = accelerator(cell["cell"]["chips"], require_tpu)
    cache_dir = compile_cache()
    import jax
    dev = devs[0]
    clock = CompileClock.get()
    b = Bench(cell, seed, clock, t_start)
    setup_s = time.perf_counter() - t_start
    compiles_before = clock.compiles

    tdir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    rec = drive(b, seconds, trace_dir=tdir, rate=rate,
                backlog_every=backlog_every)
    rec["window_compiles"] = clock.compiles - compiles_before
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))

    limits = cell["limits"]
    C = b.conf["service"]["chunk_size"]
    sample = sample_jobs(rec, b.sub["check"], limits["sample_tokens"])
    t = time.perf_counter()
    mism = round_trip(b, rec, sample)
    rt_s = time.perf_counter() - t
    rec.update(setup_s=setup_s, mean_pos=mean_position(rec, C),
               model=b.m, service=b.conf["service"],
               reference=b.ref.__name__.rsplit(".", 1)[1],
               peaks=peaks_for(dev.device_kind) if require_tpu else None)
    m, split, ref_mod = b.m, dict(b.split), b.ref
    replay = getattr(b, "replay", None)
    b.free()
    del b
    chunks, valid, prog = chunk_table(
        sample, lambda j: j["tokens"] if "tokens" in j
        else replay[j["replay"]], C)
    t = time.perf_counter()
    ref = reference_bits(ref_mod, cell["config"], m, chunks, valid,
                         limits["reference_block"])
    ref_s = time.perf_counter() - t
    got = gaps(prog, ref, valid)
    ctl = None
    if control:
        ctl = gaps(reference_bits(ref_mod, cell["config"], m, chunks, valid,
                                  limits["reference_block"], int8=True),
                   ref, valid)

    red, sp = read_trace(tdir, keep_trace) if trace else (None, None)
    rec["trace"], rec["spans"] = red, sp

    metrics = {}
    for spec in (cell["per_layer"] if trace else cell["end_to_end"]):
        v = metric_reader(spec["name"], cell["bench_dir"])(rec)
        if v is not None:
            metrics[spec["name"]] = {"value": float(v), "unit": spec["unit"]}

    attempted = len(rec["jobs"])
    failed = sum(1 for j in rec["jobs"] if "t_done" not in j or "error" in j)
    compared, correct = judge(
        {"roundtrip_mismatched_tokens": mism, "failed_jobs": failed,
         **{k: got[k] for k in limits["compare"]}},
        dict(limits, failed_jobs=0,
             roundtrip_mismatched_tokens=limits["roundtrip"]))
    if ctl is not None:
        ctl_compared, ctl_correct = judge(
            {k: ctl[k] for k in limits["compare"]}, limits)

    late = np.asarray(rec["lateness_s"] or [0.0])
    log("setup split: " + " ".join(
        f"{k}={v!r}" for k, v in dict(split, setup_s=setup_s).items()))
    log(f"window: t_close={rec['t_close']!r} jobs={attempted} "
        f"failed={failed} drain_s={rec['drain_s']!r} "
        f"poll_s={rec['poll_s']!r} compiles_in_window="
        f"{rec['window_compiles']} generator_late_s mean={float(late.mean())!r}"
        f" max={float(late.max())!r} counters={rec['counters']}")
    if rec["backlog"]:
        log(f"backlog (s, jobs outstanding): {rec['backlog']}")
    if sp:
        log_idle_split(sp)
    log(f"memory_peak_bytes={peak} compile_cache={cache_dir}")
    log(f"check: sampled_jobs={len(sample)} sampled_chunks={len(valid)} "
        f"sampled_tokens={int(valid.sum())} roundtrip_s={rt_s!r} "
        f"reference_s={ref_s!r} program={got}")
    if ctl is not None:
        for k, c in ctl_compared.items():
            log(f"control {k} = {c['value']!r} (limit {c['limit']!r})")
        log(f"control_correct = {ctl_correct}")
    for k, c in compared.items():
        log(f"compared {k} = {c['value']!r} (limit {c['limit']!r})")

    out = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
    }
    if trace and red is not None:
        from chipbench import trace as tr
        out["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = tr.breakdown(red)
    if ctl is not None:
        out.update(program=got, control=ctl_compared,
                   control_correct=bool(ctl_correct))
    out["compared"] = compared
    return out
