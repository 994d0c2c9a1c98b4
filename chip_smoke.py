#!/usr/bin/env python3
"""Chip smoke test: the loop compiler's main path on a TPU at deployment size.

  python chip_smoke.py [--seed 0]              # one chip
  python chip_smoke.py --chips 4 [--seed 0]    # four chips: the sharded path

One chip: `compile_program → run()` of pagerank on a Graph500 R-MAT graph
(scale 22, edge factor 16, 10 steps), group_by (2^27 rows into 2^20 keys),
histogram (2^26 pixels into 256 bins: the Pallas segment kernel),
kmeans_step (2^21 points, K=64) and an 8192² matrix product, dense and with
the left operand §5-packed at 50% tile density (the Pallas tile kernel);
then a `PlanServer` answering concurrent pagerank and group_by requests.
Four chips: pagerank and group_by through `compile_distributed` on a (4,)
"data" mesh in shardmap and gspmd mode, against the same programs on
device 0.  Nothing else runs in that mode.

Every input is generated from --seed in numpy and every output is checked
against a plain numpy float64 reference written here, to 1e-4 relative
(the tolerance of tests/test_core_distributed.py; normwise for the matrix
products, elementwise as well for the rest).  The run fails on any
robustness-ladder descent or retry, a whole-program trace failure, a fused
round that bailed, or a Pallas kernel in interpret mode.  The last line of
stdout is {"ok": true, "device": {"platform", "kind", "count"}}; without a
TPU the script exits non-zero and prints no such line.  One process: it
starts no subprocess, and its server thread is stopped before it exits.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

TOL = 1e-4
# op_select decisions come from committed code only: a path that never
# exists, so no stray autotune cache copied along with the tree steers them
NO_AUTOTUNE_CACHE = os.path.join(ROOT, "chip_smoke.no-autotune-cache.json")
# backends the TPU cost model must pick, so the Pallas kernels really run
TPU_EXPECT = {"histogram": "segment:pallas",
              "matmul_packed": "tiled:pallas-tiled"}
# outputs that are 8192-term inner products: where the terms cancel, the
# float32 rounding of their partial sums alone exceeds 1e-4 of |b|+1 (numpy's
# own float32 product does), so these are held to the normwise measure
NORMWISE = ("matmul", "matmul_packed")


@dataclasses.dataclass(frozen=True)
class Sizes:
    rmat_scale: int = 22            # 4,194,304 vertices
    edge_factor: int = 16           # 67,108,864 edges
    pagerank_steps: int = 10
    group_rows: int = 1 << 27
    group_keys: int = 1 << 20
    hist_pixels: int = 1 << 26
    hist_bins: int = 256
    kmeans_points: int = 1 << 21
    kmeans_k: int = 64
    matmul_n: int = 8192
    tile: int = 128                 # §5 packed tile edge
    serve_scale: int = 16           # 2^16 vertices x 16 = 2^20 edges
    serve_rows: int = 1 << 20
    serve_keys: int = 1 << 16
    serve_requests: int = 8         # per program, all in flight at once


FULL = Sizes()


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# data (numpy, from the seed) and the float64 references
# ---------------------------------------------------------------------------

def rmat_edges(rng, scale: int, edge_factor: int, a=0.57, b=0.19, c=0.19):
    """Graph500 Kronecker (R-MAT) edge list, vertex labels permuted: per
    level one quadrant draw with probabilities a, b, c, 1-a-b-c (the joint
    law of the reference generator's two draws)."""
    m = edge_factor << scale
    src = np.empty(m, np.int32)
    dst = np.empty(m, np.int32)
    weight = (1 << np.arange(scale, dtype=np.int32))[:, None]
    step = 1 << 18                          # edges per cache-sized block
    for lo in range(0, m, step):
        u = rng.random((scale, min(step, m - lo)), dtype=np.float32)
        ii = u >= a + b
        jj = ((u >= a) & ~ii) | (u >= a + b + c)
        src[lo:lo + u.shape[1]] = (ii * weight).sum(0)
        dst[lo:lo + u.shape[1]] = (jj * weight).sum(0)
    perm = rng.permutation(1 << scale).astype(np.int32)
    return perm[src], perm[dst]


def pagerank_case(rng, scale, edge_factor, steps, b=0.85):
    src, dst = rmat_edges(rng, scale, edge_factor)
    n = 1 << scale
    ins = dict(E=(src.astype(np.float32), dst.astype(np.float32)),
               P=np.full(n, 1.0 / n, np.float32), NP=np.zeros(n, np.float32),
               C=np.zeros(n, np.float32), N=n, num_steps=float(steps),
               steps=0.0, b=b)
    C = np.bincount(src, minlength=n).astype(np.float64)
    P = np.full(n, 1.0 / n)
    NP = np.zeros(n)
    with np.errstate(divide="ignore", invalid="ignore"):
        for _ in range(steps):     # P[s]/C[s] summed into NP[d]
            NP = np.bincount(dst, weights=(P / C)[src], minlength=n)
            P = (1.0 - b) / n + b * NP
    return ins, dict(P=P, NP=NP, C=C)


def group_by_case(rng, rows, keys):
    k = rng.integers(0, keys, rows, dtype=np.int32)
    v = rng.standard_normal(rows, dtype=np.float32)
    ins = dict(S=(k.astype(np.float32), v), C=np.zeros(keys, np.float32))
    return ins, dict(C=np.bincount(k, weights=v.astype(np.float64),
                                   minlength=keys))


def histogram_case(rng, pixels, bins):
    px = [rng.integers(0, bins, pixels, dtype=np.int32) for _ in range(3)]
    ins = dict(P=tuple(c.astype(np.float32) for c in px),
               R=np.zeros(bins, np.float32), G=np.zeros(bins, np.float32),
               B=np.zeros(bins, np.float32))
    ref = {n: np.bincount(c, minlength=bins).astype(np.float64)
           for n, c in zip("RGB", px)}
    return ins, ref


def kmeans_case(rng, n, k):
    """Gaussian blobs around a grid of k centres 20 apart (σ = 1), initial
    centroids perturbed from the true ones: every point's nearest centroid
    is unambiguous, so float32 and float64 assign identically."""
    g = int(np.ceil(np.sqrt(k)))
    centres = 20.0 * np.stack(np.divmod(np.arange(k), g), 1)
    lab = rng.integers(0, k, n)
    x = (centres[lab, 0] + rng.standard_normal(n)).astype(np.float32)
    y = (centres[lab, 1] + rng.standard_normal(n)).astype(np.float32)
    cx = (centres[:, 0] + 0.5 * rng.standard_normal(k)).astype(np.float32)
    cy = (centres[:, 1] + 0.5 * rng.standard_normal(k)).astype(np.float32)
    z = lambda *s: np.zeros(s, np.float32)   # noqa: E731
    ins = dict(P=(x, y), CX=cx, CY=cy, K=k, D=z(n, k),
               MinD=np.full(n, 1e30, np.float32), Cl=z(n), SX=z(k), SY=z(k),
               CN=z(k), NX=z(k), NY=z(k))
    x64, y64 = x.astype(np.float64), y.astype(np.float64)
    D = (x64[:, None] - cx.astype(np.float64)) ** 2 \
        + (y64[:, None] - cy.astype(np.float64)) ** 2
    MinD = np.minimum(D.min(axis=1), 1e30)
    cl = (k - 1) - np.argmin(D[:, ::-1], axis=1)   # last minimum, as the loop
    CN = np.bincount(cl, minlength=k).astype(np.float64)
    SX = np.bincount(cl, weights=x64, minlength=k)
    SY = np.bincount(cl, weights=y64, minlength=k)
    ref = dict(D=D, MinD=MinD, Cl=cl.astype(np.float64), SX=SX, SY=SY,
               CN=CN, NX=SX / np.maximum(CN, 1.0), NY=SY / np.maximum(CN, 1.0))
    return ins, ref


def matmul_case(rng, n, tile):
    """Dense n×n product, and the same product with the left operand's
    tiles zeroed at exactly 50% density (the §5 packed case)."""
    M = rng.standard_normal((n, n), dtype=np.float32)
    N = rng.standard_normal((n, n), dtype=np.float32)
    nt = n // tile
    keep = (rng.permutation(nt * nt) < (nt * nt) // 2).reshape(nt, nt)
    Ms = (M.reshape(nt, tile, nt, tile)
          * keep[:, None, :, None]).reshape(n, n)
    N64 = N.astype(np.float64)
    dims = dict(n=n, m=n, l=n)
    return (dict(M=M, N=N, R=np.zeros((n, n), np.float32), **dims),
            dict(R=M.astype(np.float64) @ N64),
            dict(M=Ms, N=N, R=np.zeros((n, n), np.float32), **dims),
            dict(R=Ms.astype(np.float64) @ N64))


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def close(label: str, got, ref, tol: float = TOL,
          elementwise: bool = True) -> float:
    """Worst of max|a-b|/(|b|+1) (the distributed tests' measure) and
    max|a-b|/max|b| (which still bites when the values are far below 1);
    the normwise max|a-b|/max|b| alone when not `elementwise`."""
    a = np.asarray(got, np.float64)
    b = np.asarray(ref, np.float64)
    if a.shape != b.shape:
        raise SmokeFailure(f"{label}: shape {a.shape} != reference {b.shape}")
    if not np.all(np.isfinite(a)):
        raise SmokeFailure(f"{label}: non-finite values")
    if a.size == 0:
        return 0.0
    diff = np.abs(a - b)
    err = float(np.max(diff)) / max(float(np.max(np.abs(b))), 1e-30)
    if elementwise:
        err = max(err, float(np.max(diff / (np.abs(b) + 1.0))))
    if not err <= tol:
        raise SmokeFailure(f"{label}: relative error {err:.3e} > {tol:g}")
    return err


def check_clean(label: str, cp) -> None:
    """No rung of any ladder was taken: no descent, no retry, no failed
    whole-program trace."""
    c = cp.faults.counters
    if c["descend"] or c["retry"] or cp.faults.level_reached \
            or cp.trace_failures:
        raise SmokeFailure(f"{label}: fallback taken\n{cp.explain_faults()}")


def check_rounds(label: str, dp) -> str:
    text = dp.explain_rounds()
    if "bailed" in text:
        raise SmokeFailure(f"{label}: a fused round bailed\n{text}")
    return text


def decisions(cp, tiled=()) -> list:
    """op_select decisions of the last run(); `tiled` names packed params."""
    return [ln.strip() for ln in cp.explain(tiled=tiled).splitlines()
            if "selected:" in ln]


def _device_inputs(inputs: dict) -> dict:
    """Arrays onto the device once, so run() times exclude the upload."""
    import jax
    out = {}
    for k, v in inputs.items():
        if isinstance(v, np.ndarray):
            out[k] = jax.device_put(v)
        elif isinstance(v, tuple):
            out[k] = tuple(jax.device_put(c) for c in v)
        else:
            out[k] = v                 # dims, scalars, packed matrices
    return out


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def run_single(label: str, fn, inputs: dict, ref: dict, platform: str,
               est_inputs: dict | None = None, tiled=()) -> dict:
    """compile_program → run() twice (cold, warm) → checks → one record."""
    from repro.core import compile_program
    t0 = time.perf_counter()
    cp = compile_program(fn, autotune_cache=NO_AUTOTUNE_CACHE)
    plan_s = time.perf_counter() - t0
    est = cp.estimate_memory(est_inputs if est_inputs is not None
                             else inputs)
    dev = _device_inputs(inputs)
    _, first_s = _timed(lambda: cp.run(dev))
    out, warm_s = _timed(lambda: cp.run(dev))
    check_clean(label, cp)
    errs = {k: close(f"{label}.{k}", out[k], r,
                     elementwise=label not in NORMWISE)
            for k, r in ref.items()}
    dec = decisions(cp, tiled)
    want = TPU_EXPECT.get(label) if platform == "tpu" else None
    if want is not None and not any(want in d for d in dec):
        raise SmokeFailure(f"{label}: expected {want}, got {dec}")
    rec = dict(program=label, plan_s=plan_s, first_run_s=first_s,
               warm_run_s=warm_s, memest_peak_bytes=est.peak_bytes,
               device_peak_bytes_so_far=_peak_bytes(),
               max_rel_err=max(errs.values(), default=0.0), selected=dec)
    log(f"[{label}] plan {plan_s} s  first run (trace+compile+run) "
        f"{first_s} s  warm run {warm_s} s  max rel err "
        f"{rec['max_rel_err']}")
    log(f"[{label}] memest peak {est.peak_bytes} B  device peak_bytes_in_use "
        f"(process so far) {rec['device_peak_bytes_so_far']}")
    for d in dec:
        log(f"[{label}]   {d}")
    return rec


def single_steps(sizes: Sizes, seed: int, platform: str) -> list:
    """(label, thunk) per single-device program; each thunk makes its own
    data from the seed, so a failed step leaves the others unchanged."""
    from repro.core.programs import (group_by, histogram, kmeans_step,
                                     matrix_multiplication, pagerank)
    from repro.core.tiles import pack

    def rng(i):
        return np.random.default_rng([seed, i])

    def matmuls():
        dense, dref, sparse, sref = matmul_case(rng(4), sizes.matmul_n,
                                                sizes.tile)
        recs = [run_single("matmul", matrix_multiplication, dense, dref,
                           platform)]
        import jax.numpy as jnp
        packed = dict(sparse, M=pack(jnp.asarray(sparse["M"]), sizes.tile,
                                     sizes.tile))
        return recs + [run_single("matmul_packed", matrix_multiplication,
                                  packed, sref, platform,
                                  est_inputs=sparse, tiled=("M",))]

    return [
        ("pagerank", lambda: run_single(
            "pagerank", pagerank, *pagerank_case(
                rng(0), sizes.rmat_scale, sizes.edge_factor,
                sizes.pagerank_steps), platform)),
        ("group_by", lambda: run_single(
            "group_by", group_by, *group_by_case(
                rng(1), sizes.group_rows, sizes.group_keys), platform)),
        ("histogram", lambda: run_single(
            "histogram", histogram, *histogram_case(
                rng(2), sizes.hist_pixels, sizes.hist_bins), platform)),
        ("kmeans_step", lambda: run_single(
            "kmeans_step", kmeans_step, *kmeans_case(
                rng(3), sizes.kmeans_points, sizes.kmeans_k), platform)),
        ("matmul", matmuls),
    ]


def phase_serve(sizes: Sizes, seed: int) -> dict:
    """PlanServer with pagerank and group_by: every request in flight at
    once, each answer equal to a solo run() bit for bit and to numpy."""
    from repro.core import compile_program
    from repro.core.programs import group_by, pagerank
    from repro.serve import PlanServer
    cps = {"pagerank": compile_program(pagerank,
                                       autotune_cache=NO_AUTOTUNE_CACHE),
           "group_by": compile_program(group_by,
                                       autotune_cache=NO_AUTOTUNE_CACHE)}
    reqs = []
    for i in range(sizes.serve_requests):
        rng = np.random.default_rng([seed, 1000 + i])
        reqs.append(("pagerank", *pagerank_case(
            rng, sizes.serve_scale, sizes.edge_factor,
            sizes.pagerank_steps)))
        reqs.append(("group_by", *group_by_case(
            rng, sizes.serve_rows, sizes.serve_keys)))
    server = PlanServer(cps, max_batch=sizes.serve_requests, flush_ms=50.0)
    server.start()
    try:
        t0 = time.perf_counter()
        tickets = [server.submit(p, ins) for p, ins, _ in reqs]
        answers = [t.result(timeout=300.0) for t in tickets]
        serve_s = time.perf_counter() - t0
    finally:
        server.stop()
    st = server.stats()
    bad = {k: st[k] for k in ("failed", "seq_fallbacks", "failed_flushes",
                              "bisections", "poisoned", "retries",
                              "load_shed", "deadline_expired") if st[k]}
    if bad or st["completed"] != len(reqs) \
            or server.faults.counters["descend"]:
        raise SmokeFailure(f"serve: {bad} completed={st['completed']}\n"
                           f"{server.explain_serving()}")
    errs = []
    for i, ((p, ins, ref), got) in enumerate(zip(reqs, answers)):
        solo = cps[p].run(ins)
        for k in cps[p].program.outputs:
            if not np.array_equal(np.asarray(solo[k]), got[k]):
                raise SmokeFailure(f"serve: request {i} ({p}) output {k} "
                                   f"differs from a solo run()")
        errs += [close(f"serve.{p}[{i}].{k}", got[k], r)
                 for k, r in ref.items()]
    for p, cp in cps.items():
        check_clean(f"serve.{p}", cp)
    log(f"[serve] {len(reqs)} concurrent requests in {serve_s} s "
        f"(compiles included), flushes={st['flushes']} "
        f"batch_traced={st['batch_traced']}, all equal to solo run(), "
        f"max rel err {max(errs)}")
    return dict(program="serve", requests=len(reqs), serve_s=serve_s,
                flushes=st["flushes"], max_rel_err=max(errs))


def phase_distributed(sizes: Sizes, seed: int, ndev: int) -> list:
    """pagerank and group_by over a (ndev,) "data" mesh, shardmap and
    gspmd, against the single-device program on device 0 and numpy."""
    from repro.core import compile_program
    from repro.core.distributed import compile_distributed
    from repro.core.programs import group_by, pagerank
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh((ndev,), ("data",))
    rng = np.random.default_rng(seed)
    cases = [("pagerank", pagerank, "E", *pagerank_case(
                 rng, sizes.rmat_scale, sizes.edge_factor,
                 sizes.pagerank_steps)),
             ("group_by", group_by, "S", *group_by_case(
                 rng, sizes.group_rows, sizes.group_keys))]
    recs = []
    for name, fn, bag, ins, ref in cases:
        cp = compile_program(fn, autotune_cache=NO_AUTOTUNE_CACHE)
        _, first_s = _timed(lambda: cp.run(ins))
        single, warm_s = _timed(lambda: cp.run(ins))
        check_clean(f"{name}.single", cp)
        for k, r in ref.items():
            close(f"{name}.single.{k}", single[k], r)
        log(f"[{name}.single] device 0  first run {first_s} s  warm run "
            f"{warm_s} s")
        for mode in ("shardmap", "gspmd"):
            label = f"{name}.{mode}"
            dp = compile_distributed(fn, mesh, ("data",), mode=mode,
                                     autotune_cache=NO_AUTOTUNE_CACHE)
            placed, _, _ = dp.place(ins)
            devs = {s.device for s in placed[bag][0].addressable_shards}
            if len(devs) != ndev:
                raise SmokeFailure(f"{label}: bag spans {len(devs)} devices")
            _, first_s = _timed(lambda: dp.run(ins))
            out, warm_s = _timed(lambda: dp.run(ins))
            check_clean(label, dp.cp)
            rounds = check_rounds(label, dp)
            if name == "pagerank" and mode == "shardmap" and (
                    "FusedRound" not in rounds
                    or "on-device lax.while_loop" not in rounds):
                raise SmokeFailure(f"{label}: loop not fused on device\n"
                                   f"{rounds}")
            err = max(max(close(f"{label}.{k}", out[k], r),
                          close(f"{label}.{k} vs single", out[k], single[k]))
                      for k, r in ref.items())
            log(f"[{label}] {ndev} devices  first run {first_s} s  warm "
                f"run {warm_s} s  max rel err {err}")
            for ln in rounds.splitlines():
                log(f"[{label}]   {ln}")
            recs.append(dict(program=label, first_run_s=first_s,
                             warm_run_s=warm_s, max_rel_err=err))
    return recs


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    from repro import compile_cache
    from repro.kernels import ops
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devs[0].platform!r})", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 2
    if ops._interp():
        print("chip_smoke: Pallas kernels would run in interpret mode",
              file=sys.stderr)
        return 1
    compile_cache.enable()
    log(f"chip_smoke: {len(devs)} x {devs[0].device_kind}, seed {args.seed}, "
        f"jax {jax.__version__}")
    if args.chips == 4:
        steps = [("distributed", lambda: phase_distributed(FULL, args.seed,
                                                           4))]
    else:
        steps = single_steps(FULL, args.seed, "tpu") + [
            ("serve", lambda: phase_serve(FULL, args.seed))]
    failed = run_steps(steps)
    if failed:
        print(f"chip_smoke: FAILED steps: {failed}", file=sys.stderr)
        return 1
    # count: the chips this run used (device 0, or the mesh's first four),
    # not every chip the host exposes
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": args.chips}}), flush=True)
    return 0


def run_steps(steps) -> list:
    """Run every step, even after one fails; returns the failed labels."""
    failed = []
    for label, thunk in steps:
        try:
            thunk()
        except Exception as ex:             # noqa: BLE001 — reported below
            failed.append(label)
            if not isinstance(ex, SmokeFailure):
                traceback.print_exc()
            print(f"chip_smoke: {label} FAILED: {ex}", file=sys.stderr,
                  flush=True)
    return failed


if __name__ == "__main__":
    sys.exit(main())
