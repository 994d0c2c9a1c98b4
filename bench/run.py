#!/usr/bin/env python3
"""The chip benchmark: one cell of BENCHMARK.json, one run.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (bench/configs/<config>.json: sizes and the
dataset law, bench/datasets/<dataset>.py), a traffic mix
(bench/traffic/<traffic>.json: the job, bench/jobs/<job>.py, its parameters
and the limits of its comparison) and a chip count.  Per-layer metrics are
read by bench/metrics/<metric>.py.  All are found by name, so a cell, mix or
metric is added as new files.

A run: set-up (inputs generated on the device from --seed, two input sets
from two streams of it; the program compiled, from the persistent cache when
it is warm; one warm-up job per set), then a closed-loop window with one
caller: jobs back to back, alternating the sets, until --seconds have passed
and the last job has ended.  After the window the outputs of a sample of the
jobs, drawn from the seed, are compared with a float64 numpy reference.
With --trace 1 the window runs under the JAX profiler and the per-layer
metrics are read from the trace, the benchmark's host clock and the
program's counters.  The last line of stdout is one JSON object; without an
accelerator, or with fewer chips than the cell asks for, the run exits
non-zero and prints none.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(BENCH, "traces")     # git-ignored
# op_select decides from committed code only: a path that never exists
NO_AUTOTUNE_CACHE = os.path.join(BENCH, "no-autotune-cache.json")
SAMPLE = 8            # job outputs kept for the comparison, drawn from the seed
BIG = sys.float_info.max


class BenchError(RuntimeError):
    """The run cannot measure: no result is printed."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(kind: str, name: str, bench: str = BENCH):
    path = os.path.join(bench, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list     # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, name) and m["moves"] in moved]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(os.path.join(root, cfg["file"])),
                traffic=load_json(os.path.join(
                    root, "bench", "traffic", w["traffic"] + ".json")),
                end_to_end=e2e, per_layer=per_layer)


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits (jax.random.key keeps 32)."""
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def check_device(chips: int) -> tuple[list, dict]:
    """The devices this run uses and their peaks; BenchError without an
    accelerator, with too few, or of a kind bench/peaks.json lacks."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise BenchError("no accelerator: JAX found only the CPU")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devs)}")
    peaks = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    kind = devs[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    from repro.kernels import ops
    if ops._interp():
        raise BenchError("Pallas kernels would run in interpret mode")
    return devs[:chips], peaks[kind]


class CompileLog:
    """JAX's own compile events, counted while it is open: how much of
    set-up is compiling, whether the persistent cache served it, and
    whether anything compiles in the window."""

    def __init__(self):
        self.counts: dict = {}
        self.seconds: dict = {}

    def _event(self, event, **kw):
        if event.startswith("/jax/compilation_cache/"):
            self.counts[event] = self.counts.get(event, 0) + 1

    def _duration(self, event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            self.counts[event] = self.counts.get(event, 0) + 1
            self.seconds[event] = self.seconds.get(event, 0.0) + duration

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_listener(self._event)
        monitoring.unregister_event_duration_listener(self._duration)

    def take(self) -> str:
        """What was counted since the last take, in one line."""
        parts = [f"{k.rsplit('/', 1)[1]} {v}"
                 + (f" ({self.seconds[k]} s)" if k in self.seconds else "")
                 for k, v in sorted(self.counts.items())]
        self.counts, self.seconds = {}, {}
        return ", ".join(parts) or "none"


@dataclasses.dataclass
class Window:
    attempted: int = 0
    failed: int = 0
    seconds: float = 0.0
    dispatch_s: list = dataclasses.field(default_factory=list)
    job_s: list = dataclasses.field(default_factory=list)  # completed jobs
    kept: list = dataclasses.field(default_factory=list)  # (set, outputs)
    compiles: int = 0


def run_window(runner, sets: list, seconds: float, seed: int, outputs,
               trace: bool) -> Window:
    import jax
    rng = random.Random(seed)
    w = Window()
    completed = 0
    c0 = runner.compiles()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # host spans and device ops, no Python function tracing: smaller
        # traces, and less host work added to the traced window
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            s = w.attempted % len(sets)
            w.attempted += 1
            before = runner.rungs()
            try:
                t_job = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    out = runner.run(sets[s])
                t_ret = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.wait"):
                    jax.block_until_ready(out)
            except Exception:              # noqa: BLE001 — counted, reported
                w.failed += 1
                log(f"job {w.attempted - 1} raised:\n{traceback.format_exc()}")
                continue
            w.dispatch_s.append(t_ret - t_job)
            w.job_s.append(time.perf_counter() - t_job)
            if runner.rungs() != before:
                w.failed += 1
                log(f"job {w.attempted - 1} took a ladder rung:\n"
                    f"{runner.explain()}")
                continue
            completed += 1
            kept = (s, {k: out[k] for k in outputs})
            if len(w.kept) < SAMPLE:
                w.kept.append(kept)
            else:
                j = rng.randrange(completed)
                if j < SAMPLE:
                    w.kept[j] = kept
        w.seconds = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    w.compiles = runner.compiles() - c0
    return w


def job_times(job_s: list) -> str:
    """The spread of the window's job times, in one line: whether a slow
    window is slow in every job or in a few."""
    if not job_s:
        return "no job completed"
    t = sorted(job_s)
    med = t[len(t) // 2]
    slow = [x for x in t if x > 1.5 * med]
    return (f"job times: min {t[0]} median {med} max {t[-1]} s; "
            f"{len(slow)} over 1.5x the median, "
            f"{sum(slow) - med * len(slow)} s over it in all")


def compare(job, kept_host: list, refs: dict, limits: dict) -> dict:
    """Each compared number, worst over the kept jobs, beside its limit."""
    worst: dict = {}
    for s, out in kept_host:
        for k, v in job.compare(out, refs[s]).items():
            worst[k] = max(worst.get(k, 0.0), v)
    for k, v in sorted(worst.items()):
        log(f"reading {k} = {v!r}")
    # a number that is missing or not finite reads as the largest float,
    # which JSON can carry and no limit admits
    return {k: {"value": min(worst.get(k, BIG), BIG), "limit": lim}
            for k, lim in limits.items()}


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader (bench/metrics/<name>.py) gets."""
    jobs: int                 # jobs completed in the window (all traced)
    dispatch_s: list          # host clock, run() call until it returns
    compiles: int             # program compiles inside the window
    work: dict                # the job's necessary work (jobs/<job>.work)
    peaks: dict               # bench/peaks.json entry of this device
    trace: object = None      # devtrace.Summary of the window, or None


def read_per_layer(cell: Cell, reading: Reading) -> dict:
    out = {}
    for m in cell.per_layer:
        v = load_module("metrics", m["name"]).read(reading)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def measure(cell: Cell, seed: int, seconds: float, trace: bool, *,
            require_device: bool = True, entry: str | None = None) -> dict:
    """One run of `cell`; returns the result object.  `require_device`
    False (the tests) runs on whatever JAX has, with no peaks and no
    persistent compilation cache.  `entry` puts another entry
    (bench/entries/<entry>.py) in the place of the traffic's."""
    if require_device:
        from repro import compile_cache
        compile_cache.enable()      # JAX opens it at the first compile
    import jax
    import numpy as np

    if require_device:
        devs, peaks = check_device(cell.chips)
    else:
        devs, peaks = jax.devices()[:cell.chips], {}
    cfg, traffic = cell.config, cell.traffic
    dataset = load_module("datasets", cfg["dataset"])
    job = load_module("jobs", traffic["job"])
    entry = load_module("entries", entry or traffic["entry"])
    clean = True

    with CompileLog() as clog, \
            jax.profiler.TraceAnnotation("bench.setup"):
        def stage(what):
            log(f"setup: {what} at {time.perf_counter() - T_START} s; "
                f"compiles: {clog.take()}")
        stage("started")
        sets = []
        for k in jax.random.split(seed_key(seed), 2):
            cols, domains = dataset.generate(cfg, k)
            sets.append(job.inputs(cols, domains, traffic))
            del cols
            jax.block_until_ready(sets)
            stage("input set made")
        runner = entry.Runner(job, cell.chips, NO_AUTOTUNE_CACHE)
        for ins in sets:
            jax.block_until_ready(runner.run(job.warm_inputs(ins, traffic)))
            stage("warm-up job done")
        if any(runner.rungs()):
            clean = False
            log(f"warm-up took a ladder rung:\n{runner.explain()}")
        setup_s = time.perf_counter() - T_START
        log(f"setup {setup_s} s; op_select:\n" + "\n".join(
            ln for ln in runner.explain().splitlines() if "selected:" in ln))

        w = run_window(runner, sets, seconds, seed, job.OUTPUTS, trace)
        completed = w.attempted - w.failed
        log(f"window {w.seconds} s, {w.attempted} jobs, {w.failed} failed, "
            f"{w.compiles} program compiles; JAX compiles: {clog.take()}")
        log(job_times(w.job_s))
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    work = job.work(sets[0])

    # the reference runs once the program's state is freed
    used = sorted({s for s, _ in w.kept})
    hosts = {s: job.host_data(sets[s]) for s in used}
    kept_host = [(s, {k: np.asarray(v) for k, v in out.items()})
                 for s, out in w.kept]
    del runner, sets, w.kept
    gc.collect()
    refs = {s: job.reference(hosts[s]) for s in used}
    checks = compare(job, kept_host, refs, traffic["limits"])
    correct = (clean and completed > 0 and w.failed == 0 and bool(kept_host)
               and all(c["value"] <= c["limit"] for c in checks.values()))

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": w.attempted,
              "failed": w.failed}
    if trace:
        import devtrace
        summary = devtrace.summarize(devtrace.load(
            devtrace.latest_xplane(TRACE_DIR)))
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        metrics = read_per_layer(cell, Reading(
            jobs=completed, dispatch_s=w.dispatch_s, compiles=w.compiles,
            work=work, peaks=peaks, trace=summary))
        result.update(metrics=metrics, device=device,
                      breakdown=devtrace.breakdown(summary))
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": "s"}
            elif m["name"] == "job_s" and completed:
                metrics["job_s"] = {"value": w.seconds / completed,
                                    "unit": "s"}
        result.update(metrics=metrics, device=device)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    try:
        cell = load_cell(args.workload)
        result = measure(cell, args.seed, args.seconds, bool(args.trace))
    except BenchError as ex:
        log(f"bench: {ex}")
        return 2
    for k, c in result["checks"].items():
        log(f"check {k} = {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
