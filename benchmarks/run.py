"""Benchmark harness — one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--scale 1]

Sections:
  [table1]  translation time per program (paper Table 1)
  [fig3]    generated vs hand-written JAX per program (paper Figure 3);
            --repeats N controls the best-of-N/median timing, --check
            gates >15% ratio regressions against the committed
            BENCH_programs.json (exit 1 — wired into CI)
  [sec5]    packed/tiled matrices (paper §5)
  [kernels] per shape-class timing of every SegmentReduce backend
            candidate vs the cost model's pick (DESIGN.md §8); emits
            BENCH_kernels.json so autotune decisions are inspectable
  [dispatch] whole-program compilation overhead (DESIGN.md §9): per-call
            eager vs whole run() time + warm-cache retrace counts, and —
            via a fresh subprocess that forces host devices — distributed
            pagerank/kmeans with round fusion on vs off; emits
            BENCH_dispatch.json
  [dist]    shardmap (inferred shardings) vs replicated per program on a
            forced 8-host-device mesh (DESIGN.md §6); run this section in
            a FRESH process (it forces XLA_FLAGS before importing jax);
            --check fails when shardmap is >10% slower than replicated
            on any benchmarked program (wired into CI)
  [skew]    uniform vs Zipf(1.5) key streams through the same sharded
            programs (skew-aware distribution, DESIGN.md §6) on a forced
            host-device mesh; also a FRESH-process section; emits
            BENCH_skew.json; --check fails when the Zipf stream is >20%
            slower than uniform on any program (wired into CI)
  [serve]   PlanServer throughput on the mixed pagerank + group_by +
            kmeans workload at 1/8/64 simulated clients (DESIGN.md §10);
            emits BENCH_serve.json; --check fails when 64-client
            throughput is < 3x 1-client (wired into CI)
  [faults]  robustness cost (DESIGN.md §11): serve goodput at 0/5/20%
            injected transient faults plus mid-loop checkpoint/resume
            overhead on pagerank; emits BENCH_faults.json; --check fails
            when goodput under 20%% faults drops below 0.5x fault-free
            or resume costs > 2x the uninterrupted run (chaos CI)
  [outofcore] capacity-tier cost (DESIGN.md §12): pagerank + word_count
            all-resident vs chunked streaming at 2x/10x over a simulated
            device budget (bit-identity asserted); emits
            BENCH_outofcore.json; --check fails when the 10x-over-budget
            run costs > 2.5x the all-resident run (chaos CI)
  [recovery] recovery-tier cost (DESIGN.md §13): mid-loop shard loss
            recovered by lineage recompute vs fault-free vs lineage-off
            ladder restart, plus speculative straggler re-execution on
            the injected clock — measured in a fresh subprocess that
            forces 8 host devices; emits BENCH_recovery.json; --check
            fails when the recovered run costs > 1.5x fault-free or the
            speculated straggler's effective completion is > 2x the
            straggler-free run (chaos CI)
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _aggregate_rows(runs):
    """Merge N fig3 measurement runs into per-program MEDIANS of every
    column — how the committed baseline is produced (--aggregate 3): a
    single run's ratio can sit at the noise-lucky edge of its spread,
    which would make an honest future run trip the --check gate."""
    if len(runs) == 1:
        return runs[0]
    acc: dict = {}
    order = []
    for run in runs:
        for row in run:
            if row[0] not in acc:
                order.append(row[0])
            acc.setdefault(row[0], []).append(row[1:])

    def med(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    return [(n,) + tuple(med([s[i] for s in acc[n]]) for i in range(5))
            for n in order]


def _cpu_child(module: str, marker: str):
    """Run `python -m <module> --dist` — a measurement on forced host
    devices — pinned to the CPU backend (a parent that has touched JAX
    holds the chip), and return the JSON rows after `marker`.  A child
    that fails, or prints no rows, fails the run."""
    import subprocess
    r = subprocess.run(
        [sys.executable, "-m", module, "--dist"], capture_output=True,
        text=True, cwd=_REPO, timeout=1800,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    for line in r.stdout.splitlines():
        if r.returncode == 0 and line.startswith(marker):
            return json.loads(line[len(marker):])
    sys.exit(f"[{module}] measurement subprocess FAILED "
             f"(rc={r.returncode}):\n{r.stdout[-2000:]}{r.stderr[-2000:]}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=1,
                    help="dataset scale multiplier for fig3")
    ap.add_argument("--repeats", type=int, default=5,
                    help="fig3 interleaved timing pass pairs per program; "
                         "the gated ratio is the MEDIAN of per-pair "
                         "ratios (drift-immune), with best-of-N and "
                         "median times recorded alongside")
    ap.add_argument("--aggregate", type=int, default=1,
                    help="fig3 measurement runs; per-program MEDIANS "
                         "across runs are reported and written (the "
                         "committed baseline uses 3, see README)")
    ap.add_argument("--check", action="store_true",
                    help="regression gates: fig3 ratios vs the committed "
                         "BENCH_programs.json (>15%% worse fails), and "
                         "dist shardmap vs replicated (>10%% slower "
                         "fails); exit non-zero on either")
    ap.add_argument("--sections", default="table1,fig3,sec5")
    ap.add_argument("--json-out", default=os.path.join(
        _REPO, "BENCH_programs.json"),
        help="fig3 artifact path for the perf trajectory ('' disables)")
    ap.add_argument("--kernels-json-out", default=os.path.join(
        _REPO, "BENCH_kernels.json"),
        help="kernels artifact path ('' disables)")
    ap.add_argument("--dispatch-json-out", default=os.path.join(
        _REPO, "BENCH_dispatch.json"),
        help="dispatch artifact path ('' disables)")
    ap.add_argument("--dist-json-out", default=os.path.join(
        _REPO, "BENCH_distributed.json"),
        help="dist artifact path ('' disables)")
    ap.add_argument("--skew-json-out", default=os.path.join(
        _REPO, "BENCH_skew.json"),
        help="skew artifact path ('' disables)")
    ap.add_argument("--serve-json-out", default=os.path.join(
        _REPO, "BENCH_serve.json"),
        help="serve artifact path ('' disables)")
    ap.add_argument("--faults-json-out", default=os.path.join(
        _REPO, "BENCH_faults.json"),
        help="faults artifact path ('' disables)")
    ap.add_argument("--outofcore-json-out", default=os.path.join(
        _REPO, "BENCH_outofcore.json"),
        help="outofcore artifact path ('' disables)")
    ap.add_argument("--recovery-json-out", default=os.path.join(
        _REPO, "BENCH_recovery.json"),
        help="recovery artifact path ('' disables)")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()
    sections = args.sections.split(",")
    if args.check and not {"fig3", "dist", "skew", "serve",
                           "faults", "outofcore",
                           "recovery"} & set(sections):
        ap.error("--check gates fig3, dist, skew, serve, faults, "
                 "outofcore, and/or recovery: "
                 "include one in --sections")

    if {"dist", "skew"} & set(sections):
        if len(sections) != 1:
            # forcing host devices would skew every other section's
            # timings (and the BENCH_programs.json perf trajectory)
            ap.error(f"--sections {sections[0]} must run alone (fresh "
                     "process): it forces XLA host device count before "
                     "jax loads")
        # must run before anything imports jax: forces host device count
        if "dist" in sections:
            from benchmarks import distributed
            distributed._force_devices()
        else:
            from benchmarks import skew_bench
            skew_bench._force_devices()

    if "table1" in sections:
        from benchmarks import translation_time
        print("[table1] translation time (paper Table 1; "
              "paper: DIABLO 5-14.5s, MOLD 11-340s, CASPER 10s-19h)")
        print("name,translate_ms,first_run_ms")
        for name, a, b in translation_time.rows():
            print(f"{name},{a:.2f},{b:.1f}")
        print()

    check_failed = False
    if "fig3" in sections:
        from benchmarks import programs
        baseline = None
        if args.check:
            base_path = args.json_out or os.path.join(
                _REPO, "BENCH_programs.json")
            with open(base_path) as f:     # committed ratios, read BEFORE
                baseline = {r["name"]:     # they are rewritten
                            (r["ratio"],   # median-paired estimator
                             r["generated_us"] / r["handwritten_us"])
                            for r in json.load(f)["rows"]}
        print(f"[fig3] generated vs hand-written (paper Figure 3; "
              f"best of {args.repeats}"
              + (f", median of {args.aggregate} runs" if args.aggregate > 1
                 else "") + ")")
        print("name,generated_us,handwritten_us,ratio,"
              "gen_median_us,hand_median_us")
        rows = _aggregate_rows(
            [programs.rows(args.scale, repeats=args.repeats)
             for _ in range(max(1, args.aggregate))])
        for name, tg, th, r, tgm, thm in rows:
            print(f"{name},{tg:.0f},{th:.0f},{r:.2f},{tgm:.0f},{thm:.0f}")
        print()
        if args.json_out:
            with open(args.json_out, "w") as f:
                json.dump({"section": "fig3", "scale": args.scale,
                           "unit": "us_per_call", "repeats": args.repeats,
                           "aggregated_runs": max(1, args.aggregate),
                           "rows": [{"name": n, "generated_us": round(tg, 1),
                                     "handwritten_us": round(th, 1),
                                     "ratio": round(r, 3),
                                     "generated_median_us": round(tgm, 1),
                                     "handwritten_median_us": round(thm, 1)}
                                    for n, tg, th, r, tgm, thm in rows]},
                          f, indent=1)
            print(f"[fig3] wrote {args.json_out}")
        if baseline is not None:
            # a program regresses only when BOTH estimators agree — the
            # median-of-pairs ratio AND the best-of-N ratio each >15%
            # worse than the SAME estimator's committed baseline (either
            # one alone flips on machine noise, and each must be held to
            # its own bar) — AND the regression reproduces on an
            # independent re-measurement of just the flagged programs.
            def _regressions(rws):
                return {n: (baseline[n][0], r, tg / th)
                        for n, tg, th, r, _m1, _m2 in rws
                        if n in baseline
                        and r > baseline[n][0] * 1.15
                        and tg / th > baseline[n][1] * 1.15}
            bad = _regressions(rows)
            if bad:
                print(f"[fig3] {len(bad)} candidate regression(s): "
                      f"{','.join(sorted(bad))}; re-measuring to confirm")
                rerun = programs.rows(args.scale, repeats=args.repeats,
                                      only=frozenset(bad))
                bad = {n: v for n, v in _regressions(rerun).items()
                       if n in bad}
            if bad:
                check_failed = True
                print("[fig3] REGRESSION GATE FAILED (median-paired AND "
                      "best-of-N ratios >15% worse than baseline, "
                      "confirmed by re-measurement):")
                for n, (old, new, new_min) in sorted(bad.items()):
                    print(f"  {n}: {old:.3f} -> {new:.3f} "
                          f"(best-of-N {new_min:.3f})")
            else:
                print(f"[fig3] regression gate OK "
                      f"({len(baseline)} baselines, none >15% worse)")
            # absolute pagerank gate (ISSUE 5): the iterative flagship
            # must stay within 1.15x of hand-written on BOTH estimators
            # (whole-program compilation holds it near parity; before the
            # fill-gather + loop-body work it sat at 1.233)
            _PR_GATE = 1.15

            def _pr_bad(rws):
                return {n: (r, tg / th) for n, tg, th, r, _m1, _m2 in rws
                        if n == "pagerank" and r > _PR_GATE
                        and tg / th > _PR_GATE}
            prb = _pr_bad(rows)
            if prb:
                print(f"[fig3] pagerank over the {_PR_GATE:.2f} absolute "
                      "gate; re-measuring to confirm")
                prb = _pr_bad(programs.rows(args.scale,
                                            repeats=args.repeats,
                                            only=frozenset(["pagerank"])))
            if prb:
                check_failed = True
                r, rmin = prb["pagerank"]
                print(f"[fig3] PAGERANK GATE FAILED (ratio {r:.3f} / "
                      f"best-of-N {rmin:.3f} > {_PR_GATE:.2f} on both "
                      "estimators, confirmed by re-measurement)")
            else:
                print(f"[fig3] pagerank gate OK (<= {_PR_GATE:.2f})")
        print()

    if "sec5" in sections:
        from benchmarks import tiled
        print("[sec5] packed/tiled matrices (paper §5)")
        print("name,us_per_call")
        for name, t in tiled.rows():
            print(f"{name},{t:.0f}")
        print()

    if "kernels" in sections:
        from benchmarks import kernels_bench
        print("[kernels] SegmentReduce backend candidates per shape class "
              "(DESIGN.md §8; None = skipped by work cap)")
        krows = kernels_bench.rows()
        kernels_bench.print_rows(krows)
        print()
        if args.kernels_json_out:
            import jax
            with open(args.kernels_json_out, "w") as f:
                json.dump({"section": "kernels", "unit": "us_per_call",
                           "platform": jax.default_backend(),
                           "rows": krows}, f, indent=1)
            print(f"[kernels] wrote {args.kernels_json_out}")
        print()

    if "dispatch" in sections:
        from benchmarks import dispatch_bench
        print("[dispatch] run() per-call overhead, eager vs whole-program "
              "(DESIGN.md §9)")
        srows = dispatch_bench.single_rows()
        dispatch_bench.print_single(srows)
        print()
        print("[dispatch] distributed round fusion on vs off "
              "(fresh subprocess, forced host devices)")
        drows = _cpu_child("benchmarks.dispatch_bench",
                           dispatch_bench._DIST_MARKER)
        print(json.dumps(drows, indent=1))
        print()
        if args.dispatch_json_out:
            with open(args.dispatch_json_out, "w") as f:
                json.dump({"section": "dispatch", "unit": "us/ms per call",
                           "single_device": srows, "distributed": drows},
                          f, indent=1)
            print(f"[dispatch] wrote {args.dispatch_json_out}")
        print()

    if "dist" in sections:
        from benchmarks import distributed
        print("[dist] shardmap (inferred shardings) vs replicated "
              f"({distributed.mesh_devices()} forced host devices)")
        print("name,shardmap_ms,replicated_ms,sharded_dense_arrays")
        rows = distributed.rows(args.scale)
        for name, a, b, k in rows:
            print(f"{name},{a:.1f},{b:.1f},{k}")
        print()
        if args.dist_json_out:
            with open(args.dist_json_out, "w") as f:
                json.dump({"section": "dist", "scale": args.scale,
                           "devices": distributed.mesh_devices(),
                           "unit": "ms_per_run",
                           "rows": [{"name": n,
                                     "shardmap_ms": round(a, 2),
                                     "replicated_ms": round(b, 2),
                                     "sharded_dense_arrays": k}
                                    for n, a, b, k in rows]}, f, indent=1)
            print(f"[dist] wrote {args.dist_json_out}")
        if args.check and distributed.check_rows(rows, args.scale):
            check_failed = True

    if "skew" in sections:
        from benchmarks import skew_bench
        print("[skew] uniform vs Zipf(1.5) key streams, shardmap "
              f"({skew_bench.mesh_devices()} forced host devices)")
        print("name,uniform_ms,zipf_ms,ratio,salted")
        rows = skew_bench.rows(args.scale)
        for name, u, z, s in rows:
            print(f"{name},{u:.1f},{z:.1f},{z / u:.2f},{int(s)}")
        print()
        if args.skew_json_out:
            with open(args.skew_json_out, "w") as f:
                json.dump(skew_bench.to_json(rows, args.scale), f, indent=1)
            print(f"[skew] wrote {args.skew_json_out}")
        if args.check and skew_bench.check_rows(rows, args.scale):
            check_failed = True

    if "serve" in sections:
        from benchmarks import serve_bench
        print("[serve] PlanServer, mixed pagerank+group_by+kmeans "
              "workload, closed-loop clients (DESIGN.md §10)")
        rows = serve_bench.rows()
        serve_bench.print_rows(rows)
        print()
        if args.serve_json_out:
            with open(args.serve_json_out, "w") as f:
                json.dump(serve_bench.to_json(rows), f, indent=1)
            print(f"[serve] wrote {args.serve_json_out}")
        if args.check and serve_bench.check_rows(rows):
            check_failed = True

    if "faults" in sections:
        from benchmarks import faults_bench
        print("[faults] serve goodput under injected transients + "
              "mid-loop resume overhead (DESIGN.md §11)")
        rows = faults_bench.rows()
        faults_bench.print_rows(rows)
        print()
        if args.faults_json_out:
            with open(args.faults_json_out, "w") as f:
                json.dump(faults_bench.to_json(rows), f, indent=1)
            print(f"[faults] wrote {args.faults_json_out}")
        if args.check and faults_bench.check_rows(rows):
            check_failed = True

    if "outofcore" in sections:
        from benchmarks import outofcore_bench
        print("[outofcore] all-resident vs chunked streaming at 2x/10x "
              "over a simulated device budget (DESIGN.md §12)")
        rows = outofcore_bench.rows()
        outofcore_bench.print_rows(rows)
        print()
        if args.outofcore_json_out:
            with open(args.outofcore_json_out, "w") as f:
                json.dump(outofcore_bench.to_json(rows), f, indent=1)
            print(f"[outofcore] wrote {args.outofcore_json_out}")
        if args.check and outofcore_bench.check_rows(rows):
            check_failed = True

    if "recovery" in sections:
        from benchmarks import recovery_bench
        print("[recovery] lineage shard recovery vs fault-free vs "
              "lineage-off restart, + speculative stragglers "
              "(DESIGN.md §13; fresh subprocess, forced host devices)")
        rrows = _cpu_child("benchmarks.recovery_bench",
                           recovery_bench._MARKER)
        recovery_bench.print_rows(rrows)
        print()
        if args.recovery_json_out:
            with open(args.recovery_json_out, "w") as f:
                json.dump(recovery_bench.to_json(rrows), f, indent=1)
            print(f"[recovery] wrote {args.recovery_json_out}")
        if args.check and recovery_bench.check_rows(rrows):
            check_failed = True

    if check_failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
