"""Operator selection: cost-modeled + autotuned backend choice for the
group-by-⊕ hot path (DESIGN.md §8).

The paper's Rule-16 group-by translation is the one operator family where
a single static lowering cannot be "as fast as the hardware allows": the
right materialization of `SegmentReduce` depends on the shape of the
reduction (rows N, segments K, value columns D), the dtype, the platform,
and — distributed — on where the destination lives and how many rows each
shard holds.  This module owns that choice.  Candidate backends:

  scatter   native scatter-⊕ with drop semantics (dest.at[keys].⊕); the
            all-rounder on CPU, serialized per duplicate key on TPU
  sort      one key/value sort, then jax.ops.segment_⊕ with
            indices_are_sorted — the classic GPU shape; loses on CPU
            (measured, see BENCH_kernels.json)
  onehot    [N, K] one-hot × [N, D] values on the MXU via dot_general —
            group-by as matmul; wins for small K everywhere (measured ~6x
            over scatter at K=16 even on CPU BLAS)
  pallas    the blocked Pallas one-hot-MXU kernel (kernels/segment_reduce)
            — the TPU-native form; interpret-mode (CPU) cost is python-
            level, so the model only picks it on a real TPU backend

plus the distributed-exchange choice for a sharded group-by round
(`psum_scatter` vs allreduce+slice) and the §5 packed-matmul choice
(`pallas-tiled` vs unpack+einsum).

Two modes, one interface:

  cost      (default) an analytical model over shape classes — abstract
            per-element costs per platform, CPU constants calibrated
            against measurement (benchmarks/kernels_bench.py), TPU/GPU
            constants first-principles estimates.  Deterministic: same
            shapes → same decision (golden-testable).
  autotune  measure every candidate once per SHAPE CLASS ((N, K, D)
            bucketed to powers of two, dtype, op, dest sharding) on the
            first encounter, persist the winner to an on-disk cache
            (`.repro_autotune.json` by default) that later sessions — and
            CI — reload, so the timing cost is paid once per class ever.

`force:<backend>` short-circuits both (tests, A/B benchmarks, and the
legacy `use_kernels=True` flag, which maps to `force:pallas`).

Decisions are made at TRACE time — concrete shapes are known there, and a
decision changes only the traced computation, never its result (every
backend implements the same ⊕-merge with paper §3.4 drop semantics).  The
executor records each decision; `explain()`/`explain_rounds()` print it
per node, which is the subsystem's observable contract.
"""
from __future__ import annotations

import json
import math
import os
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

# candidate sets per monoid ⊕ (correctness, not preference: onehot only
# sums; sort covers every monoid via jax.ops.segment_*; pallas does + via
# the MXU dot and min/max via the one-hot select path)
SEGMENT_CANDIDATES = {
    "+": ("scatter", "sort", "onehot", "pallas"),
    "min": ("scatter", "sort", "pallas"),
    "max": ("scatter", "sort", "pallas"),
    "*": ("scatter", "sort"),
}

EXCHANGE_CANDIDATES = ("psum_scatter", "allreduce")
CONTRACT_CANDIDATES = ("pallas-tiled", "unpack-einsum")

# hot-key salting sub-destination factors (the S in salt*K + key); "none"
# is always a candidate — it is the status quo
SALT_FACTORS = (4, 8, 16)

CACHE_FILE = ".repro_autotune.json"


def _bucket(x: int) -> int:
    """Ceil-log2 shape-class bucket: 1→0, 2→1, 3..4→2, 5..8→3, ..."""
    return max(0, int(x) - 1).bit_length()


@dataclass(frozen=True)
class Decision:
    """One resolved backend choice, with its provenance for explain()."""
    backend: str
    source: str          # "cost" | "autotune" | "cache" | "forced"
    why: str = ""

    def __str__(self) -> str:
        tail = f": {self.why}" if self.why else ""
        return f"{self.backend}[{self.source}{tail}]"


# ---------------------------------------------------------------------------
# the analytical cost model
# ---------------------------------------------------------------------------
# Abstract cost in µs: fixed dispatch overhead + per-element rates.  The
# cpu row is CALIBRATED against measurement on the container (see
# BENCH_kernels.json; scatter ~0.12µs/row, onehot ~0.002µs/cell, argsort
# ~0.05µs/(row·log₂N), Pallas interpret mode is python-level — modeled as
# a prohibitive fixed cost so it is never cost-picked off-TPU).  tpu/gpu
# rows are first-principles estimates (scatter serializes on duplicate
# keys; the MXU streams one-hot cells at matmul rate) — autotune mode
# replaces them with measurement the first time a class is seen on the
# real hardware.

_COSTS = {
    "cpu": dict(fixed=60.0, scatter_row=0.12, sort_row=0.05,
                onehot_cell=0.002, pallas_cell=0.002, pallas_fixed=2e5,
                coll_row=0.004, coll_fixed=400.0, dest_shard_fixed=1500.0,
                tile_mxu=math.inf, einsum_cell=4e-5, unpack_cell=1.5e-3,
                dup_row=0.0, salt_fold=0.004),
    "tpu": dict(fixed=5.0, scatter_row=1.0, sort_row=0.01,
                onehot_cell=2e-4, pallas_cell=1.2e-5, pallas_fixed=30.0,
                coll_row=1e-4, coll_fixed=10.0, dest_shard_fixed=5.0,
                tile_mxu=1.5e-5, einsum_cell=1.5e-5, unpack_cell=2e-4,
                dup_row=1.0, salt_fold=2e-4),
    "gpu": dict(fixed=10.0, scatter_row=0.05, sort_row=0.008,
                onehot_cell=3e-4, pallas_cell=math.inf, pallas_fixed=math.inf,
                coll_row=2e-4, coll_fixed=20.0, dest_shard_fixed=50.0,
                tile_mxu=math.inf, einsum_cell=2e-5, unpack_cell=3e-4,
                dup_row=0.01, salt_fold=3e-4),
}
# dup_row: extra per-row cost when rows COLLIDE on one destination row —
# hardware scatter serializes duplicate-key updates (severe on TPU, atomics
# contend mildly on GPU, the CPU loop is sequential regardless, so 0: cost
# mode never salts on CPU).  salt_fold: per-cell cost of the [K, S] ⊕-fold
# that merges the salted sub-destinations back.


def _segment_cost(c: dict, backend: str, n: int, k: int, d: int) -> float:
    nd = n * max(1, d)
    nkd = n * k * max(1, d)
    if backend == "scatter":
        return c["fixed"] + c["scatter_row"] * nd
    if backend == "sort":
        return c["fixed"] + c["sort_row"] * n * (math.log2(max(2, n)) +
                                                 max(1, d))
    if backend == "onehot":
        return c["fixed"] + c["onehot_cell"] * nkd
    if backend == "pallas":
        return c["pallas_fixed"] + c["pallas_cell"] * nkd
    return math.inf


def probe_hot_fraction(keys, cap: int = 4096) -> float:
    """Run-time skew probe: the fraction of rows held by the most frequent
    key in a host-side prefix sample of the key column (≤ `cap` rows — a
    numpy unique over 4096 int32s is microseconds, paid once per distinct
    (shapes, skew-bucket) signature because the resulting decision is part
    of the compile-cache key).  A prefix sample is exact for the
    distributions that matter here: a hot key that holds ≥ 1/8 of a
    uniformly-ordered stream holds ≈ the same share of any prefix."""
    import numpy as np
    a = np.asarray(keys)[:cap].reshape(-1)
    if a.size == 0:
        return 0.0
    _, counts = np.unique(a, return_counts=True)
    return float(counts.max()) / float(a.size)


def _hot_bucket(hot_frac: float) -> int:
    """Skew bucket for the salt shape class: eighths of the stream held by
    the hottest key (0 = uniform … 8 = single-key)."""
    return max(0, min(8, int(hot_frac * 8.0 + 0.5)))


# ---------------------------------------------------------------------------
# autotune measurement (standalone impls mirroring the executor backends)
# ---------------------------------------------------------------------------

_MEASURE_CELL_CAP = 2e8     # onehot materializes N×K: skip beyond this
_MEASURE_INTERP_CAP = 1e7   # pallas interpret mode is python-level: skip
#                             big classes off-TPU instead of stalling the
#                             first autotuned run for minutes


def _measure_segment(backend: str, n: int, k: int, d: int, op: str,
                     dtype) -> float:
    """µs per call of one backend on synthetic data of the class shape.
    Mirrors the executor's materialization closely enough for ranking."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    cells = n * k * max(1, d)
    if backend == "onehot" and cells > _MEASURE_CELL_CAP:
        return math.inf
    if backend == "pallas" and jax.default_backend() != "tpu" \
            and cells > _MEASURE_INTERP_CAP:
        return math.inf
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, k, n), jnp.int32)
    vshape = (n,) if d <= 1 else (n, d)
    vals = jnp.asarray(rng.standard_normal(vshape)).astype(dtype)
    dest = jnp.zeros((k,) if d <= 1 else (k, d), dtype)

    if backend == "scatter":
        from .lower import _scatter_op
        fn = jax.jit(lambda de, i, v: _scatter_op(de.at[i], op)(
            v, mode="drop"))
    elif backend == "sort":
        seg = {"+": jax.ops.segment_sum, "min": jax.ops.segment_min,
               "max": jax.ops.segment_max, "*": jax.ops.segment_prod}[op]

        def fn(de, i, v, _seg=seg):
            from .lower import COMBINE
            if v.ndim == 2:     # the executor ravels key × column into one id
                d_ = v.shape[1]
                i = (i[:, None] * d_ + jnp.arange(d_)).reshape(-1)
            i, vs = jax.lax.sort_key_val(i, v.reshape(-1), is_stable=True)
            return COMBINE[op](de, _seg(vs, i, num_segments=de.size,
                                        indices_are_sorted=True)
                               .reshape(de.shape))
        fn = jax.jit(fn)
    elif backend == "onehot":
        def fn(de, i, v, _k=k):
            acc = v.dtype if jnp.issubdtype(v.dtype, jnp.integer) \
                else jnp.float32
            oh = (i[:, None] == jnp.arange(_k)[None, :]).astype(acc)
            v2 = v[:, None] if v.ndim == 1 else v
            part = jax.lax.dot_general(oh, v2.astype(acc),
                                       (((0,), (0,)), ((), ())),
                                       preferred_element_type=acc)
            part = part[:, 0] if v.ndim == 1 else part
            return de + part.astype(de.dtype)
        fn = jax.jit(fn)
    elif backend == "pallas":
        from ..kernels import ops as kops

        def fn(de, i, v, _k=k):
            from .lower import COMBINE
            return COMBINE[op](de, kops.segment_reduce(i, v, _k, op=op)
                               .astype(de.dtype))
        fn = jax.jit(fn)
    else:
        return math.inf

    try:
        jax.block_until_ready(fn(dest, ids, vals))   # compile
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            r = fn(dest, ids, vals)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / reps * 1e6
    except Exception:
        return math.inf          # a candidate that cannot run never wins


# ---------------------------------------------------------------------------
# the selector
# ---------------------------------------------------------------------------

class OpSelector:
    """Resolves backend choices per shape class.  One instance per
    CompiledProgram (shared with its executor); the on-disk cache is
    shared across instances via its path.

    Autotune MEASURES segment classes only (they need no mesh).  The
    exchange / reduce-dest / contract lookups consult the same cache, but
    their entries are supplied externally — hand-written or emitted by
    mesh-owning tooling — as the override channel for platforms where the
    analytical model's ranking is wrong."""

    def __init__(self, mode: str = "cost",
                 cache_path: Optional[str] = CACHE_FILE,
                 platform: Optional[str] = None):
        self.mode = mode
        self.cache_path = cache_path
        self._platform = platform
        self._cache: dict = {}
        self._dirty = False
        if mode.startswith("force:"):
            self.forced: Optional[str] = mode.split(":", 1)[1]
        else:
            self.forced = None
            if mode not in ("cost", "autotune"):
                raise ValueError(f"unknown op_select mode {mode!r}")
        # the cache is the override channel in EVERY mode: autotune writes
        # measured segment classes into it, and hand-/tool-supplied
        # entries (exchange, dest, contract classes) must be honored by
        # cost mode too — a cost-mode lookup hit reports source "cache"
        if cache_path and os.path.exists(cache_path):
            self.load(cache_path)

    # ---- platform / cost table ----
    @property
    def platform(self) -> str:
        if self._platform is None:
            import jax
            self._platform = jax.default_backend()
        return self._platform

    def may_pick_pallas(self) -> bool:
        """Whether a Pallas backend can be chosen: by the TPU's cost row,
        a pin, or a cached decision (the CPU's cost row prices the
        kernels' interpret mode out)."""
        return (self.platform == "tpu"
                or (self.forced or "").startswith("pallas")
                or any(str(v.get("backend", "")).startswith("pallas")
                       for v in self._cache.values()))

    def _costs(self) -> dict:
        try:
            return _COSTS[self.platform]
        except KeyError:
            raise ValueError(
                f"op_select has no cost table for platform "
                f"{self.platform!r} (known: {sorted(_COSTS)})") from None

    # ---- cache ----
    def load(self, path: str) -> None:
        try:
            with open(path) as f:
                blob = json.load(f)
            if blob.get("version") == 1 and \
                    blob.get("platform") == self.platform:
                self._cache.update(blob.get("decisions", {}))
        except (OSError, ValueError):
            pass                 # unreadable cache never breaks execution

    def save(self, path: Optional[str] = None) -> None:
        path = path or self.cache_path
        if not path:
            return
        with open(path, "w") as f:
            json.dump({"version": 1, "platform": self.platform,
                       "decisions": dict(sorted(self._cache.items()))},
                      f, indent=1)
        self._dirty = False

    def _remember(self, key: str, entry: dict) -> None:
        self._cache[key] = entry
        self._dirty = True
        if self.cache_path:
            try:
                self.save()
            except OSError:
                pass             # read-only FS: keep the in-memory decision

    # ---- segment reduce ----
    def segment_class(self, n: int, k: int, d: int, op: str, dtype,
                      dest_dist: str) -> str:
        return (f"segment|{op}|{dtype}|n{_bucket(n)}|k{_bucket(k)}"
                f"|d{_bucket(max(1, d))}|{dest_dist}")

    def choose_segment(self, *, n: int, k: int, d: int, op: str, dtype,
                       dest_dist: str = "REP",
                       candidates: Optional[tuple] = None) -> Decision:
        cands = candidates or SEGMENT_CANDIDATES.get(op, ("scatter",))
        if op == "+" and self.platform == "tpu" \
                and np.issubdtype(np.dtype(str(dtype)), np.integer):
            # the MXU has no int32 dot: Mosaic refuses the kernel's
            # exact-int sum, so integer sums take an XLA backend
            cands = tuple(b for b in cands if b != "pallas")
        if self.forced is not None and self.forced in cands:
            return Decision(self.forced, "forced")
        # a forced backend the candidate set does not admit (e.g.
        # force:onehot on a min-group-by) falls through to the model —
        # pinning only applies where the pin is correct
        key = self.segment_class(n, k, d, op, str(dtype), dest_dist)
        hit = self._cache.get(key)
        if hit is not None and hit.get("backend") in cands:
            return Decision(hit["backend"], "cache", key)
        if self.mode == "autotune":
            us = {b: _measure_segment(b, n, k, max(1, d), op, dtype)
                  for b in cands}
            best = min(us, key=us.get)
            self._remember(key, {"backend": best, "shape": [n, k, d],
                                 "us": {b: (round(t, 1) if
                                            math.isfinite(t) else None)
                                        for b, t in us.items()}})
            return Decision(best, "autotune", key)
        c = self._costs()
        cost = {b: _segment_cost(c, b, n, k, max(1, d)) for b in cands}
        best = min(cost, key=cost.get)
        return Decision(best, "cost", key)

    # ---- hot-key salting (skew-aware group-by, DESIGN.md §6) ----
    def salt_class(self, n: int, k: int, op: str, nshards: int,
                   hot_frac: float) -> str:
        return (f"salt|{op}|n{_bucket(n)}|k{_bucket(k)}|p{nshards}"
                f"|h{_hot_bucket(hot_frac)}")

    def choose_salt(self, *, n: int, k: int, op: str, nshards: int = 1,
                    hot_frac: float = 0.0) -> Decision:
        """Should this group-by salt its hot keys — spread each key over S
        sub-destinations (`salt*K + key`) and ⊕-fold the [S, K] partial
        back — and at which S?  Salting trades a k·S fold (and k·S partial
        memory) against the duplicate-update serialization a skewed key
        column induces in hardware scatters: a key holding fraction h of n
        rows forces h·n colliding updates on one destination row, and
        salting divides that chain by S.  The class is keyed on the PROBED
        skew bucket (`probe_hot_fraction`), so a cache entry pinned for a
        hot class never fires on uniform data.  The CPU cost row has
        dup_row=0 (the scatter loop is sequential either way), so cost
        mode only ever salts where collisions actually serialize; tests
        and A/B runs pin decisions via `PlanConfig.skew_salting`
        ("force:<S>") or the `SegmentReduce.salt` static hint instead."""
        key = self.salt_class(n, k, op, nshards, hot_frac)
        hit = self._cache.get(key)
        if hit is not None:
            return Decision(hit["backend"], "cache", key)
        # skew guard: a key is only "hot" when it holds several times its
        # fair 1/K share — below that, the collision chain is the inherent
        # n/K every group-by pays, and salting can only add fold cost
        if hot_frac * max(1, k) < 4.0:
            return Decision("none", "cost", key)
        c = self._costs()
        # only EXCESS collisions beyond the balanced chain serialize extra
        dup = c["dup_row"] * max(0.0, hot_frac - 1.0 / max(1, k)) * n
        cost = {"none": dup}
        for s in SALT_FACTORS:
            cost[f"salt:{s}"] = c["fixed"] + dup / s + c["salt_fold"] * k * s
        best = min(cost, key=cost.get)
        return Decision(best, "cost", key)

    # ---- distributed exchange (sharded group-by rounds) ----
    def exchange_class(self, k: int, d: int, op: str, nshards: int,
                       n_local: int) -> str:
        return (f"exchange|{op}|k{_bucket(k)}|d{_bucket(max(1, d))}"
                f"|p{nshards}|n{_bucket(max(1, n_local))}")

    def choose_exchange(self, *, k: int, d: int, op: str, nshards: int,
                        n_local: int = 1, dest_dist: str = "ONED_ROW"
                        ) -> Decision:
        """The cross-shard ⊕ of a dense [K(,D)] partial.  For a REP
        destination (and non-+ monoids, which have no reduce-scatter
        primitive) allreduce is the only candidate.  For a ONED_ROW `+`
        destination the analytical model makes reduce-scatter dominant BY
        CONSTRUCTION — it moves strictly less data than allreduce+slice
        (K·D/P received per shard vs K·D everywhere), so the cost
        comparison can only flip through a CACHE entry: platforms whose
        reduce-scatter lowering underperforms (observed on the XLA host
        backend under some shapes) can pin `allreduce` per exchange class
        in the autotune cache file; `_measure` tooling does not auto-time
        collectives (it has no mesh), so these entries are supplied by
        hand or by mesh-owning benchmarks.  The small-K regime where
        neither exchange pays is handled upstream by
        `choose_reduce_dest` demoting the destination to REP."""
        if self.forced is not None and self.forced in EXCHANGE_CANDIDATES:
            return Decision(self.forced, "forced")
        if dest_dist != "ONED_ROW" or op != "+":
            return Decision("allreduce", "cost",
                            "only candidate for this dest/op")
        key = self.exchange_class(k, d, op, nshards, n_local)
        hit = self._cache.get(key)
        if hit is not None:
            return Decision(hit["backend"], "cache", key)
        return Decision("psum_scatter", "cost", key)

    # ---- reduce-destination placement (sharded group-by rounds) ----
    def dest_class(self, k: int, d: int, op: str, nshards: int) -> str:
        return f"dest|{op}|k{_bucket(k)}|d{_bucket(max(1, d))}|p{nshards}"

    def choose_reduce_dest(self, *, k: int, d: int, op: str, nshards: int,
                           n_local: int = 1) -> Decision:
        """Dense-partial-exchange vs local-scatter-then-psum: should a
        group-by DESTINATION that only ever receives unaligned reduces
        live as ONED_ROW row blocks (partial-⊕ then reduce-scatter; each
        shard keeps K/P rows) or stay REP (partial-⊕ then allreduce)?
        Sharding pays a fixed per-run placement/dispatch overhead for the
        K/P-row layout and wins back K·D·(P-1)/P exchange volume and
        memory — so it loses exactly where the paper's shuffle loses:
        small K.  distributed.py applies the decision only to arrays the
        plan never uses in an aligned round (dist_analysis.
        demotable_dests), so REP here never forfeits an alignment win."""
        if self.forced is not None and self.forced in ("shard", "replicate"):
            return Decision(self.forced, "forced")
        key = self.dest_class(k, d, op, nshards)
        hit = self._cache.get(key)
        if hit is not None:
            return Decision(hit["backend"], "cache", key)
        c = self._costs()
        kd = k * max(1, d)
        shard = c["dest_shard_fixed"] + c["coll_fixed"] + c["coll_row"] * kd
        rep = c["coll_fixed"] + 2.0 * c["coll_row"] * kd
        best = "shard" if shard <= rep else "replicate"
        return Decision(best, "cost", key)

    # ---- §5 packed contraction ----
    def choose_contract(self, *, m: int, k: int, n: int,
                        candidates: tuple = CONTRACT_CANDIDATES) -> Decision:
        """Packed-lhs matmul: the block-sparse Pallas kernel on the tiles
        vs unpacking and contracting on the dense einsum path.  Keyed on
        the dense flop volume; the Pallas rate is the target-hardware MXU
        (∞ off-TPU: interpret mode is python-level)."""
        if self.forced is not None and self.forced in candidates:
            return Decision(self.forced, "forced")
        key = f"contract|m{_bucket(m)}|k{_bucket(k)}|n{_bucket(n)}"
        hit = self._cache.get(key)
        if hit is not None and hit.get("backend") in candidates:
            return Decision(hit["backend"], "cache", key)
        c = self._costs()
        flops = m * k * n
        pallas = c["tile_mxu"] * flops
        einsum = c["einsum_cell"] * flops + c["unpack_cell"] * m * k
        best = "pallas-tiled" if pallas <= einsum else "unpack-einsum"
        return Decision(best, "cost", key)
