"""Distributed execution of compiled loop programs over a device mesh —
the paper's DISC backend, retargeted from Spark shuffles to TPU collectives
(DESIGN.md §4, §6).

Execution consumes the SAME physical plan (CompiledProgram.plan) as the
single-device run(), through the public executor interface; bag
offsets/limits and the dense-array analogues (row offsets, logical row
limits, axis overrides) are plan parameters (lower.ExecContext), not
lowerer state.

* shard_map (paper-faithful operator mapping): bags shard over the dp
  axes, and — per the distribution-analysis pass (dist_analysis.py) —
  dense arrays inferred ONED_ROW/TWOD_BLOCK shard as contiguous dim-0 row
  blocks too, instead of replicating.  Each plan node runs as one of:

    aligned store round    MapExpr/Scatter whose leading destination key IS
                           the round axis: every shard writes only its own
                           row block; no collective at all.
    aligned reduce round   AxisReduce/EinsumContract/TiledMatmul keyed by
                           the round axis: local partial-⊕ into the local
                           block; no collective.
    unaligned reduce round local partial-⊕ into a dense [K(, D)] partial
                           BEFORE any exchange, then `psum` (REP
                           destination), or — per the operator-selection
                           subsystem (op_select.py, DESIGN.md §8) —
                           `psum_scatter` / allreduce+slice (ONED_ROW
                           destination), the decision keyed on (K, D, ⊕,
                           shard count, shard-local rows).  The partial
                           itself is computed by whichever SegmentReduce
                           backend the selector picks for the SHARD-LOCAL
                           (N/P, K) shape class.  This is the
                           reduction-based replacement for the paper's
                           shuffle-based group-by.  When the trace-time
                           hot-key probe (or a static hint) salts the
                           group-by (DESIGN.md §6), the shard-local
                           partial is computed over salt*K+key
                           sub-destinations and ⊕-folded back to [K]
                           BEFORE the exchange — the wire format never
                           changes.
    rebalance round        plan.Rebalance (ONED_VAR → ONED_ROW): per-shard
                           live-row counts exchange via psum, exclusive
                           cumsum assigns every live row its global slot,
                           and one psum_scatter all-to-all restores equal
                           blocks — exact (pure data movement, no ⊕).
                           Elided when the array is already balanced or
                           replicated; explain_rounds() prints the
                           per-shard counts and balance factor either way.
    replicated             everything else — identical on all shards; also
                           the guaranteed fallback whenever a runtime shape
                           guard fails.  Correct regardless of placement:
                           outside shard_map the env holds global arrays
                           and XLA resharding is transparent.

  Reads inside a round localize when the analysis proved them aligned with
  the round axis (the shard's row block serves every access); otherwise a
  ONED_ROW operand is `all_gather`ed on entry — the only place a gather
  collective is ever inserted, exactly where the analysis says a read
  crosses shards.  A `Fused` node still runs all its parts in ONE
  shard_map round (mixed aligned/unaligned parts allowed).

  Inside an aligned reduce round the executor keeps the MXU contraction
  path PER SHARD: aligned operands are their local blocks (slice at local
  0), replicated ones a bounds-certified lax.dynamic_slice window — the
  certificates come from the distribution analysis
  (dist_analysis.shard_slice_certificates) plus the padded-extent bound in
  ExecContext.axis_overrides, so a dynamic slice is only ever emitted when
  it provably cannot clamp.  `explain_rounds()` prints, per node, the
  round strategy, the slice certificates, and the per-shard operator the
  executor actually traced (e.g. ``mxu-einsum`` vs ``fallback:dense-grid``
  — the observable contract that generated rounds run jnp.einsum, not the
  dense iteration grid).

  Round fusion (pass 11, DESIGN.md §9): a `plan.FusedRound` region runs
  as ONE jit+shard_map program — members execute sequentially inside the
  traced body with their collectives (psum / psum_scatter / all_gather)
  placed between them, instead of one dispatch per node.  A SeqLoop whose
  whole body is one region runs as an ON-DEVICE lax.while_loop inside
  that same program whenever its condition reads only replicated state —
  zero per-iteration host syncs (the host-driven loop with one blocking
  condition sync per iteration remains the fallback, and a
  fully-replicated body short-circuits through the single-device
  lax.while_loop).  Guard failures fall back to per-member rounds;
  fusion never changes results, only dispatch.

Bags AND ONED_ROW dense arrays whose dim-0 length is not divisible by the
shard count are PADDED with zero rows to the next multiple, block by block:
each device receives its rows and only the tail blocks get zeros, so no
padded copy of the whole array is built.  A column that already lies in
equal row blocks on the mesh (a `RowBlocks`, or an evenly divisible array
with the dp sharding) is taken as it is, moving no bytes.  The original
length travels as a bag limit / array limit and the executor masks reads
and drops writes beyond it, so padding can never change a result (the
paper's §3.4 empty-bag semantics are enforced against the LOGICAL bound).
Padded outputs are sliced back to their logical length on return.

Bag columns keep their dtype from input to keys: int32 vertex ids stay
exact at and above 2**24, where float32 ids would not.

Host spans (`TraceAnnotation`, as in `core/lower.py`): `repro.run` around
`run()` (the ladder included), `repro.place` around `place()`,
`repro.round` around each round's call until it returns (the enqueue).
Device scopes: `Gather:<array>` on each all_gather of a row-block array,
`Exchange:<dest>` on each cross-shard ⊕ (psum / psum_scatter).

`shard_dense=False` (or `PlanConfig.infer_distributions=False`) restores
REP-everything — the pre-analysis behaviour and the ⊥ of the lattice.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import faults as F
from . import plan
from .dist_analysis import (Dist, aligned_reads, leading_key_var,
                            round_axis, shard_slice_certificates)
from .lower import (COMBINE, CompiledProgram, ExecContext, _span, identity,
                    salt_for_node)

_STORE_NODES = (plan.MapExpr, plan.Scatter)
_ALIGNABLE_REDUCES = (plan.AxisReduce, plan.EinsumContract, plan.TiledMatmul)


class RowBlocks:
    """A column already laid out in equal dim-0 row blocks on a mesh, its
    tail zero-padded to a multiple of the shard count, with its logical
    row count: how a caller hands `place()` a bag too large to pad inside
    `run()`.  `place()` takes `data` as it is and masks rows at and beyond
    `rows`; everything else (`np.asarray`, `jnp.asarray`, `.astype`) sees
    the logical rows only."""

    def __init__(self, data, rows: int):
        if not 0 <= rows <= data.shape[0]:
            raise ValueError(f"RowBlocks: {rows} logical rows, "
                             f"{data.shape[0]} physical")
        self.data = data
        self.rows = int(rows)

    @property
    def shape(self) -> tuple:
        return (self.rows,) + tuple(self.data.shape[1:])

    def __array__(self, dtype=None, copy=None):
        import numpy as np
        out = np.asarray(self.data)[:self.rows]
        return out if dtype is None else out.astype(dtype)

    def __jax_array__(self):
        return self.data[:self.rows]

    def astype(self, dtype):
        return self.__jax_array__().astype(dtype)

    def block_until_ready(self):
        self.data.block_until_ready()
        return self


class DistributedProgram:
    def __init__(self, cp: CompiledProgram, mesh, dp_axes=("data",),
                 mode: str = "shardmap", shard_dense: bool = True):
        if mode != "shardmap":
            raise ValueError(f"unknown distributed mode {mode!r}: "
                             f"only 'shardmap' is supported (gspmd was "
                             f"removed)")
        self.cp = cp
        self.mesh = mesh
        self.dp = tuple(dp_axes)
        self.mode = mode
        self.dp_n = 1
        for a in self.dp:
            self.dp_n *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
        # placement = inferred distribution, capped at ONED_ROW: TWOD_BLOCK
        # records 2-D legality but both executors place row blocks today
        self.dists = dict(cp.dists) if shard_dense else \
            {a: Dist.REP for a in cp.dists}
        self.placements = {a: min(d, Dist.ONED_ROW)
                           for a, d in self.dists.items()}
        # arrays the plan only ever touches as unaligned reduce dests /
        # cross-shard reads: place() may demote them to REP per run when
        # the op_select cost model says a sharded destination doesn't pay
        # for their concrete size (dense-partial + reduce-scatter vs
        # local-scatter + psum, DESIGN.md §8).  Placement-only: results
        # never change, and arrays with any aligned use are never touched.
        from .dist_analysis import demotable_dests
        self._demotable = demotable_dests(cp.plan, cp.program) \
            if shard_dense else {}
        self._base_placements = dict(self.placements)
        self._demoted: dict = {}        # name → Decision, per run
        # compiled shard_map round per (node, strategy, static params):
        # SeqLoop iterations and repeated run() calls reuse the traced
        # round instead of paying trace+compile every time.  Fused regions
        # (plan.FusedRound, pass 11) share the cache; the trace/hit
        # counters are the compile-cache observability explain_rounds()
        # reports (DESIGN.md §9)
        self._round_cache: dict = {}
        self._round_traces = 0
        self._round_hits = 0
        # region ids whose fused execution failed a runtime guard THIS run
        # (per-member fallback taken): don't re-attempt every loop iteration
        self._fused_bail: set = set()
        # id(node) → human-readable round strategy of the LAST run(), and
        # id(leaf) → the per-shard materialization that round used.  Both
        # refreshed on every node execution — cache-hit rounds restore the
        # snapshot taken when their round was traced (_round_notes), so
        # explain_rounds() stays accurate even when classification changed
        # between runs or a single-device run touched the shared executor
        # in between.
        self._strategy: dict = {}
        self._decisions: dict = {}
        self._strategy_by_key: dict = {}
        self._round_notes: dict = {}
        # env-independent node facts (round axis, aligned reads, gather
        # names): expression trees are walked once per node, not once per
        # SeqLoop iteration
        self._static_cache: dict = {}
        # skew observability (explain_rounds "balance:" lines): per-run
        # per-shard live row counts + max/mean factor for every ONED_VAR /
        # rebalanced array, and the analysis' insert-vs-elide decision
        self._rebalanced = frozenset(
            n.dest for n in _walk_plan(cp.plan)
            if isinstance(n, plan.Rebalance))
        self._balance: dict = {}
        # failure policy (DESIGN.md §11): the ledger and retry policy are
        # SHARED with the wrapped CompiledProgram — one ladder per program,
        # whichever layer descends it.  _force_rep is the REP-everything
        # ladder level: place() replicates every dense array (the ⊥ of the
        # distribution lattice, same as shard_dense=False) for one run.
        self.faults = cp.faults
        self.policy = cp.policy
        self._force_rep = False
        # ---- surgical recovery (DESIGN.md §13) ----
        # shard index → faults.clock() time of its LAST loss: a second
        # loss of the same shard inside policy.shard_loss_ttl_s means the
        # worker is flapping — escalate to the ladder instead of
        # recomputing onto a corpse again.  Deliberately NOT reset per
        # run: flapping spans runs.
        self._shard_loss: dict = {}
        self.lineage_enabled = getattr(cp.config, "lineage", True)
        # straggler speculation: ≤1 backup execution per straggling round
        # label per run (first finisher wins, loser cancelled)
        self.speculative = getattr(cp.config, "speculative", True)
        self._spec_done: set = set()

    def _placed_oned(self, name) -> bool:
        # ONED_VAR counts: variable-length arrays still shard as equal
        # physical row blocks — only their LOGICAL live lengths differ
        # (tracked by the array limit and masked like every padded array)
        return self.placements.get(name, Dist.REP) >= Dist.ONED_VAR

    # ------------------------- input placement -------------------------
    def place(self, inputs: dict):
        """Shard bags and ONED_ROW dense arrays over dp as row blocks
        (`_row_blocks`: dim 0 zero-padded to a multiple of the shard
        count), replicate the rest.  Returns (placed, bag_limits,
        array_limits); the limit dicts map each padded name to its logical
        dim-0 length — consumers MUST mask rows beyond the limit (run()
        threads them through ExecContext)."""
        with _span("repro.place"):
            return self._place(inputs)

    def _row_blocks(self, v):
        """`v` as equal dim-0 row blocks over dp, and its logical length
        when the blocks carry pad rows (else None).  A `RowBlocks`, or an
        array whose length divides evenly and that already has this
        sharding, is taken as it is: no bytes move.  Otherwise each device
        receives its own rows; only the blocks at the tail get zero rows,
        so no padded copy of the whole array is ever built."""
        sh = NamedSharding(self.mesh, P(self.dp))
        if isinstance(v, RowBlocks):
            if v.data.shape[0] % self.dp_n:
                raise ValueError(
                    f"RowBlocks of {v.data.shape[0]} rows do not split "
                    f"into {self.dp_n} equal blocks")
            data = jax.device_put(v.data, sh)
            return data, (v.rows if v.rows < data.shape[0] else None)
        arr = jnp.asarray(v)
        n = int(arr.shape[0])
        if n % self.dp_n == 0:
            return jax.device_put(arr, sh), None
        blk = -(-n // self.dp_n)
        shape = (blk * self.dp_n,) + tuple(arr.shape[1:])
        blocks = []
        for dev, idx in sh.addressable_devices_indices_map(shape).items():
            lo = idx[0].start or 0
            part = arr[min(lo, n):min(lo + blk, n)]
            if part.shape[0] < blk:
                part = jnp.concatenate([part, jnp.zeros(
                    (blk - part.shape[0],) + part.shape[1:], part.dtype)])
            blocks.append(jax.device_put(part, dev))
        return jax.make_array_from_single_device_arrays(shape, sh,
                                                        blocks), n

    def _place(self, inputs: dict):
        out = {}
        bag_limits: dict[str, int] = {}
        array_limits: dict[str, int] = {}
        # per-run placement decision for demotion-neutral reduce dests
        # (shapes are known here): shard vs replicate is an op_select call
        self.placements = dict(self._base_placements)
        self._demoted = {}
        if self._force_rep:
            # REP-everything ladder level: every dense array replicates
            # (bags still shard — they are the iteration space); the
            # demotion loop below is vacuous since nothing is placed ONED
            self.placements = {a: Dist.REP for a in self.placements}
        import numpy as _np
        for name, t in self.cp.program.params.items():
            if t.kind not in ("vector", "matrix", "map") \
                    or name not in self._demotable \
                    or not self._placed_oned(name):
                continue
            shp = _np.shape(inputs[name])
            if not shp:
                continue
            d_rest = 1
            for d_ in shp[1:]:
                d_rest *= int(d_)
            dec = self.cp.selector.choose_reduce_dest(
                k=int(shp[0]), d=d_rest, op=self._demotable[name],
                nshards=self.dp_n)
            if dec.backend == "replicate":
                self.placements[name] = Dist.REP
                self._demoted[name] = dec
        for name, t in self.cp.program.params.items():
            v = inputs[name]
            if t.kind == "bag":
                placed = [self._row_blocks(c)
                          for c in (v if isinstance(v, tuple) else (v,))]
                out[name] = tuple(c for c, _ in placed)
                if placed[0][1] is not None:
                    bag_limits[name] = placed[0][1]
            elif t.kind == "dim":
                out[name] = int(v)
            elif t.kind in ("vector", "matrix", "map") \
                    and self._placed_oned(name):
                out[name], lim = self._row_blocks(v)   # row blocks
                if lim is not None:
                    array_limits[name] = lim
            else:
                arr = jnp.asarray(v)
                out[name] = jax.device_put(
                    arr, NamedSharding(self.mesh, P()))  # broadcast join
        return out, bag_limits, array_limits

    # ------------------------- shardmap mode -------------------------
    def _psum(self, part, op: str):
        if op == "+":
            return jax.lax.psum(part, self.dp)
        if op == "min":
            return -jax.lax.pmax(-part, self.dp)
        if op == "max":
            return jax.lax.pmax(part, self.dp)
        raise NotImplementedError(op)

    def _combine_shard(self, part, op: str, shard, dest_oned: bool,
                       exchange: str = "psum_scatter", dest: str = ""):
        """Cross-shard ⊕ of an unaligned partial: psum for a replicated
        destination; for a row-block destination the exchange the
        operator-selection subsystem chose — reduce-scatter (each shard
        receives its K/P rows) or allreduce + local slice (the only
        correct form for non-+ monoids, which have no reduce-scatter
        primitive).  Its ops carry the scope `Exchange:<dest>`."""
        F.site("dist.exchange", op=op, dest_oned=dest_oned,
               exchange=exchange)
        with jax.named_scope(f"Exchange:{dest}"):
            if not dest_oned:
                return self._psum(part, op)
            if op == "+" and exchange == "psum_scatter":
                return jax.lax.psum_scatter(part, self.dp,
                                            scatter_dimension=0, tiled=True)
            full = self._psum(part, op)
            blk = full.shape[0] // self.dp_n
            return jax.lax.dynamic_slice_in_dim(full, shard * blk, blk,
                                                axis=0)

    def _gather(self, v, name: str):
        """All-gather of a row-block array inside a round (the read
        crosses shards), under the scope `Gather:<name>`."""
        with jax.named_scope(f"Gather:{name}"):
            return jax.lax.all_gather(v, self.dp, axis=0, tiled=True)

    # ------------------- rebalance rounds (ONED_VAR → ONED_ROW) ----------
    def _rebalance_local(self, x, shard, lim):
        """The rebalance round body, inside a shard_map trace: per-shard
        size exchange (one-hot `psum` of live-row counts), exclusive-cumsum
        global offsets, scatter of live rows to their balanced global
        positions, then a `psum_scatter` redistribution back to equal row
        blocks.  Each target position receives exactly ONE nonzero addend
        (every other shard contributes the zero buffer row), so the
        composition is an exact all-to-all, not an approximate reduction —
        bit-identical results on canonical front-packed layouts."""
        blk = x.shape[0]
        npad = blk * self.dp_n
        rows = shard * blk + jnp.arange(blk)
        live = rows < lim
        cnt = jnp.sum(live.astype(jnp.int32))
        # size exchange: every shard learns every live count
        counts = jax.lax.psum(
            jnp.where(jnp.arange(self.dp_n) == shard, cnt, 0), self.dp)
        start = (jnp.cumsum(counts) - counts)[shard]   # exclusive cumsum
        pos = start + jnp.cumsum(live.astype(jnp.int32)) - 1
        pos = jnp.where(live, pos, npad)               # dead rows drop
        buf = jnp.zeros((npad,) + tuple(x.shape[1:]), x.dtype)
        buf = buf.at[pos].add(x, mode="drop")
        return jax.lax.psum_scatter(buf, self.dp, scatter_dimension=0,
                                    tiled=True)

    def _shard_counts(self, npad: int, lim):
        """Host-side mirror of the size exchange (for observability): the
        logical live row count each shard holds under the canonical
        front-packed layout, plus the max/mean balance factor."""
        blk = npad // self.dp_n
        if lim is None:
            lim = npad
        counts = [max(0, min(blk, lim - s * blk)) for s in range(self.dp_n)]
        mean = sum(counts) / len(counts)
        factor = (max(counts) / mean) if mean else float("inf")
        return counts, factor

    def _exec_rebalance(self, node, env, array_limits):
        """Run a plan.Rebalance as its own cached jit+shard_map round (the
        fused-region path inlines `_rebalance_local` instead).  Elided —
        with an explain_rounds note — when the destination is replicated
        (nothing to balance) or carries no limit (blocks already equal)."""
        dest = node.dest
        if not self._placed_oned(dest):
            self._strategy[id(node)] = "rebalance: elided (replicated dest)"
            return
        v = jnp.asarray(env[dest])
        npad = int(v.shape[0])
        blk = npad // self.dp_n
        lim = array_limits.get(dest)
        if lim is None:
            self._strategy[id(node)] = (
                f"rebalance: elided (already balanced, {blk} rows × "
                f"{self.dp_n} shards)")
            return
        cache_key = ("rebalance", id(node), tuple(v.shape), str(v.dtype),
                     lim)
        fn = self._round_cache.get(cache_key)
        if fn is None:
            def local_fn(x, _lim=lim):
                shard = 0
                for a in self.dp:
                    shard = shard * self.mesh.shape[a] + \
                        jax.lax.axis_index(a)
                return self._rebalance_local(x, shard, _lim)
            fn = jax.jit(jax.shard_map(local_fn, mesh=self.mesh,
                                       in_specs=(P(self.dp),),
                                       out_specs=P(self.dp)))
            self._round_cache[cache_key] = fn
            self._round_traces += 1
        else:
            self._round_hits += 1
        prev = env[dest]
        env[dest] = fn(prev)
        counts, factor = self._shard_counts(npad, lim)
        self._strategy[id(node)] = (
            f"rebalance(size-exchange psum + all-to-all psum_scatter)"
            f"→{dest}; rows/shard={counts} balance={factor:.2f}")
        self._shard_lost_site(
            node, "rebalance", env, [(dest, "rebalance")], {dest: prev},
            lambda _fn=fn, _p=prev: {dest: _fn(_p)},
            unit="rebalance round")

    # ---- per-node round classification (runtime shape guards) ----
    def _rows(self, name, env) -> int:
        v = env[name]
        col = v[0] if isinstance(v, tuple) else v
        return int(jnp.shape(col)[0])

    def _round_spec(self, node, env):
        """Decide how to run `node`: None = replicated; else a dict with
        the round axis, per-part kinds (store / aligned / reduce) and the
        read classification (localize vs all_gather).  Every guard failure
        degrades to a coarser-but-correct strategy, never to an error."""
        parts = list(node.parts) if isinstance(node, plan.Fused) else [node]
        dests_set = {p.dest for p in parts}
        space = node.space
        static = self._static_cache.get(id(node))
        if static is None:
            axis = round_axis(node if not isinstance(node, plan.Fused)
                              else parts[0])
            static = (axis,
                      aligned_reads(node, axis) if axis is not None
                      else frozenset(),
                      _gather_names(node))
            self._static_cache[id(node)] = static
        axis, aligned, gather_names = static
        rng = None
        if space.has_bag:
            if axis is None and not plan.is_reduce(node):
                return None
            axis_rows = self._rows(next(
                a.bag for a in space.axes if a.kind == "bag"), env) \
                if axis is not None else None
        else:
            if axis is None:
                return None
            aspec = next(a for a in space.axes if a.var == axis)
            try:
                lo = self.cp.executor.static_int(aspec.lo, env)
                hi = self.cp.executor.static_int(aspec.hi, env)
            except Exception:
                return None
            if lo != 0 or hi <= 0:
                return None
            axis_rows = hi + (-hi) % self.dp_n
            # (block, limit, total): no mask needed when the rows tile
            # evenly (limit=None); `total` = padded global extent, the
            # static bound certifying per-shard dynamic slices of
            # replicated operands (lower._sliced_operand, DESIGN.md §7)
            rng = (axis_rows // self.dp_n,
                   hi if axis_rows != hi else None,
                   axis_rows)

        def dest_aligned(p):
            return (axis is not None
                    and leading_key_var(p) == axis
                    and self._placed_oned(p.dest)
                    and self._rows(p.dest, env) == axis_rows)

        kinds = []
        for p in parts:
            if isinstance(p, _STORE_NODES):
                # stores run replicated unless every shard writes (and
                # reads, for read-modify-writes) strictly within its block
                if not dest_aligned(p):
                    return None
                if p.dest in gather_names and p.dest not in aligned:
                    return None            # self-read not block-local
                kinds.append("store")
            elif plan.is_reduce(p):
                if isinstance(p, _ALIGNABLE_REDUCES) and dest_aligned(p):
                    kinds.append("aligned")
                elif space.has_bag:
                    kinds.append("reduce")
                else:
                    return None            # range round: no psum source
            else:
                return None
        # localized reads must tile exactly like the round axis
        local = frozenset(n for n in aligned
                          if n not in dests_set
                          and self._placed_oned(n)
                          and self._rows(n, env) == axis_rows)
        return {"parts": parts, "kinds": kinds, "axis": axis, "rng": rng,
                "local": local, "axis_rows": axis_rows}

    def _exec_shardmap(self, nodes, env, limits, array_limits):
        cp = self.cp
        for node in nodes:
            if isinstance(node, plan.SeqLoop):
                # best: the whole loop as ONE shard_map program with an
                # on-device lax.while_loop (fused body, collectives inside
                # — zero per-iteration host syncs)
                if len(node.body) == 1 \
                        and isinstance(node.body[0], plan.FusedRound) \
                        and self._exec_fused(node.body[0], env, limits,
                                             array_limits, loop=node):
                    continue
                # next: a fully-replicated body needs no collectives at
                # all — run the loop through the single-device executor
                # (one on-device lax.while_loop; the old path paid a
                # blocking host sync on the condition EVERY iteration)
                if self._loop_replicated(node, env):
                    self._strategy[id(node)] = (
                        "on-device lax.while_loop (replicated body, "
                        "0 host syncs)")
                    cp.execute(env, bag_limits=limits,
                               array_limits=array_limits, nodes=[node])
                    for b in plan.flatten(node.body):
                        self._decisions.update(self._part_notes(b))
                    continue
                # fallback: host-driven loop, body nodes distributed
                # recursively with one condition sync per iteration
                syncs = 0
                while bool(cp.executor.eval_scalar(node.cond, env)):
                    syncs += 1
                    self._exec_shardmap(node.body, env, limits, array_limits)
                self._strategy[id(node)] = \
                    f"host-driven ({syncs + 1} condition syncs)"
                continue

            if isinstance(node, plan.FusedRound):
                if self._exec_fused(node, env, limits, array_limits):
                    continue
                # a runtime guard failed: per-member rounds (old behaviour)
                self._exec_shardmap(node.parts, env, limits, array_limits)
                continue

            if isinstance(node, plan.Rebalance):
                self._exec_rebalance(node, env, array_limits)
                continue

            spec = self._round_spec(node, env) \
                if (plan.is_reduce(node) or isinstance(node, _STORE_NODES)) \
                else None
            if spec is None:
                # replicated execution (identical result on all shards)
                self._strategy[id(node)] = "replicated"
                cp.execute(env, bag_limits=limits,
                           array_limits=array_limits, nodes=[node])
                self._decisions.update(self._part_notes(node))
                continue
            self._run_round(node, spec, env, limits, array_limits)

    def _loop_replicated(self, node, env) -> bool:
        """True when every leaf of the SeqLoop body classifies replicated
        (no round axis anywhere): the whole loop can run as ONE
        single-device lax.while_loop dispatch instead of a host-driven
        loop that syncs on the condition every iteration."""
        for b in plan.flatten(node.body):
            if isinstance(b, plan.SeqLoop):
                if not self._loop_replicated(b, env):
                    return False
                continue
            if plan.is_reduce(b) or isinstance(b, _STORE_NODES):
                if self._round_spec(b, env) is not None:
                    return False
        return True

    def _call_round(self, fn, args, site_name, label):
        """Execute a traced round/fused program under the failure policy:
        the injection site fires per attempt, transients retry at this
        level (bounded, backoff), and the round's slowness feeds the
        straggler watchdog.  Capacity/deterministic errors re-raise —
        descending is the caller's move (per-member bail for fused, the
        run() ladder for rounds).

        `fn(*args)` returns once the round is enqueued, so its host time
        says nothing of how long the devices take, and timing the round
        to completion would cost a host sync per round.  The watchdog is
        therefore fed the delay the fault layer reports at the round's
        site (a `slow` fault), never an enqueue time: a fault-free run
        never flags a straggler, whatever the clock reads.

        A flagged straggler additionally triggers speculative
        re-execution (DESIGN.md §13): at most ONE backup copy of the
        flagged round per label per run, first finisher wins, the loser
        is cancelled.  Both copies run the same traced executable on the
        same operands, so adopting the faster one never changes results —
        speculation only buys back the tail latency a slow worker cost."""
        slow = 0.0

        def attempt():
            nonlocal slow
            slow += F.site(site_name, label=label)
            return fn(*args)
        with _span("repro.round"):
            out = F.run_with_retries(attempt, policy=self.policy,
                                     ledger=self.faults, label=label)
        straggled = self.faults.note_time(label, slow)
        if straggled and self.speculative and label not in self._spec_done:
            self._spec_done.add(label)
            t1 = self.faults.clock()
            backup = fn(*args)        # no injection site: the backup runs
            #                           on a different (healthy) worker
            dt2 = self.faults.clock() - t1
            if dt2 < slow:
                saved = slow - dt2
                self.faults.spec_saved_s += saved
                self.faults.record(
                    "speculative", label,
                    f"backup won: {dt2 * 1e3:.1f}ms vs straggler "
                    f"{slow * 1e3:.1f}ms (saved {saved * 1e3:.1f}ms); "
                    f"straggler copy cancelled")
                out = backup
            else:
                self.faults.record(
                    "speculative", label,
                    f"original finished first ({slow * 1e3:.1f}ms); "
                    f"backup cancelled after {dt2 * 1e3:.1f}ms")
        return out

    def _run_round(self, node, spec, env, limits, array_limits):
        cp = self.cp
        parts, kinds = spec["parts"], spec["kinds"]
        axis, rng, local = spec["axis"], spec["rng"], spec["local"]
        dests = [p.dest for p in parts]
        params = cp.program.params
        reads = sorted(set(node.reads) - set(dests))
        # dims are static python ints (they define extents): close over
        # them — as shard_map operands they would arrive as tracers
        dims = {n: env[n] for n in reads
                if n in params and params[n].kind == "dim"}
        names = [n for n in reads if n not in dims]
        bagnames = node.space.bag_names
        # ONED_ROW reads the analysis could NOT prove aligned cross shards:
        # pass them as blocks and all_gather on entry
        gathered = tuple(n for n in names
                         if n not in bagnames and n not in local
                         and self._placed_oned(n))
        in_specs = []
        args = []
        for n in names:
            v = env[n]
            if n in bagnames:
                in_specs.append(tuple(P(self.dp) for _ in v))
            elif n in local or n in gathered:
                in_specs.append(P(self.dp))
            else:
                in_specs.append(P() if not isinstance(v, tuple)
                                else tuple(P() for _ in v))
            args.append(v)
        store_dests = [p.dest for p, k in zip(parts, kinds) if k == "store"]
        for d in store_dests:
            in_specs.append(P(self.dp))
            args.append(env[d])

        dest_shapes = tuple(jnp.shape(env[d]) for d in dests)
        dest_dtypes = tuple(jnp.asarray(env[d]).dtype for d in dests)
        node_lims = {b: limits[b] for b in bagnames if b in limits}
        arr_lims = {n: array_limits[n]
                    for n in set(names) | set(dests) if n in array_limits}
        dest_oned = {d: self._placed_oned(d) for d in dests}
        out_specs = tuple(
            P(self.dp) if k in ("store", "aligned") or dest_oned[p.dest]
            else P()
            for p, k in zip(parts, kinds))

        # operator selection for the round's exchanges (DESIGN.md §8): the
        # cross-shard ⊕ of every unaligned reduce part is a cost-model /
        # autotune decision keyed on (K, D, op, shard count, shard-local
        # rows, dest sharding) — dense-partial + reduce-scatter vs
        # allreduce + local slice.  Static at round-build time (shapes are
        # concrete here), so the choice is part of the traced round and of
        # its cache key.
        n_loc = (spec["axis_rows"] or self.dp_n) // self.dp_n
        exchanges = {}
        for p, k in zip(parts, kinds):
            if k == "reduce":
                shp = jnp.shape(env[p.dest])
                d_rest = 1
                for d_ in shp[1:]:
                    d_rest *= int(d_)
                exchanges[p.dest] = self.cp.selector.choose_exchange(
                    k=int(shp[0]) if shp else 1, d=d_rest, op=p.op,
                    nshards=self.dp_n, n_local=n_loc,
                    dest_dist="ONED_ROW" if dest_oned[p.dest] else "REP")

        # run-time hot-key probe (skew salting): resolved against the
        # concrete key columns HERE, outside the trace — the factor is
        # part of the cache key, so a skewed and a uniform stream of the
        # same shapes trace different rounds
        salts = {}
        for p in parts:
            s = salt_for_node(p, env, cp.selector,
                              getattr(cp.config, "skew_salting", "auto"),
                              nshards=self.dp_n, bag_limits=limits)
            if s > 1:
                salts[p.dest] = s

        # everything local_fn closes over, so the traced round is reusable
        cache_key = (id(node), tuple(kinds), tuple(names),
                     tuple(store_dests), gathered, tuple(sorted(local)),
                     tuple(sorted(node_lims.items())),
                     tuple(sorted(arr_lims.items())),
                     tuple(sorted(dims.items())),
                     dest_shapes, dest_dtypes,
                     spec["axis"], spec["rng"],
                     tuple(sorted(self._demoted)),
                     tuple(sorted((d, x.backend)
                                  for d, x in exchanges.items())),
                     tuple(sorted(salts.items())))
        rlabel = f"round:{type(node).__name__}"
        # everything a block-restricted shard recompute of THIS round
        # needs (surgical recovery, DESIGN.md §13)
        rec = {"spec": spec, "names": tuple(names),
               "bagnames": frozenset(bagnames),
               "store_dests": tuple(store_dests), "dims": dims,
               "node_lims": node_lims, "arr_lims": arr_lims,
               "salts": salts}

        def replay(_fn=None, _args=tuple(args), _parts=parts,
                   _kinds=kinds):
            res2 = _fn(*_args)
            out2 = {}
            for p, k2, r in zip(_parts, _kinds, res2):
                out2[p.dest] = r if k2 == "store" else \
                    COMBINE[p.op](jnp.asarray(pre[p.dest]), r)
            return out2

        fn = self._round_cache.get(cache_key)
        if fn is not None:
            self._round_hits += 1
            results = self._call_round(fn, args, "dist.round_exec", rlabel)
            # restore the trace-time snapshot: the cached round re-runs
            # exactly what was traced, whatever happened in between
            self._strategy[id(node)] = self._strategy_by_key[cache_key]
            self._decisions.update(self._round_notes[cache_key])
            pre = {p.dest: env[p.dest] for p in parts}
            self._apply(parts, kinds, results, env)
            self._shard_lost_site(
                node, rlabel, env, list(zip(dests, kinds)), pre,
                partial(replay, _fn=fn), rec)
            return

        # trace-time only (cache hits skip it, like the trace itself):
        # record the round strategy + slice certificates for explain_rounds
        self._strategy[id(node)] = self._round_desc(
            parts, kinds, axis, exchanges, dest_oned, gathered, local)

        def local_fn(*vals, _parts=parts, _kinds=kinds,
                     _names=tuple(names), _stores=tuple(store_dests),
                     _bags=tuple(bagnames), _gather=gathered,
                     _local=tuple(local), _lims=node_lims, _alims=arr_lims,
                     _dims=dims, _shapes=dest_shapes, _dtypes=dest_dtypes,
                     _axis=axis, _rng=rng,
                     _exch={d: x.backend for d, x in exchanges.items()},
                     _salts=salts):
            e2 = dict(zip(_names + _stores, vals))
            e2.update(_dims)
            # globalize indexes: shard-local row r is offset + r (needed
            # when a bag/axis index appears in keys or values)
            shard = 0
            for a in self.dp:
                shard = shard * self.mesh.shape[a] + jax.lax.axis_index(a)
            for n in _gather:      # analysis: this read crosses shards
                e2[n] = self._gather(e2[n], n)
            offs = {b: shard * e2[b][0].shape[0] for b in _bags}
            row_offs = {n: shard * e2[n].shape[0] for n in _local}
            axis_ov = {}
            if _rng is not None:
                blk, lim, total = _rng
                axis_ov[_axis] = (shard * blk, blk, lim, total)
            outs = []
            for p, k, shp, dt in zip(_parts, _kinds, _shapes, _dtypes):
                ro = dict(row_offs)
                # alignment certificates: localized reads tile exactly like
                # the round axis (checked in _round_spec), and store/aligned
                # destinations by construction — their local dim-0 block IS
                # the axis window, so per-shard slices start at local 0
                cert = set(_local)
                if k == "store":
                    ro[p.dest] = shard * e2[p.dest].shape[0]
                    cert.add(p.dest)
                    ctx = ExecContext(offs, _lims, ro, _alims, axis_ov,
                                      frozenset(cert), _salts)
                    outs.append(cp.executor.run_node(p, e2, ctx))
                elif k == "aligned":
                    blk0 = shp[0] // self.dp_n
                    e2[p.dest] = jnp.full((blk0,) + tuple(shp[1:]),
                                          identity(p.op, dt))
                    ro[p.dest] = shard * blk0
                    cert.add(p.dest)
                    ctx = ExecContext(offs, _lims, ro, _alims, axis_ov,
                                      frozenset(cert), _salts)
                    outs.append(cp.executor.run_node(p, e2, ctx))
                else:
                    e2[p.dest] = jnp.full(shp, identity(p.op, dt))
                    ctx = ExecContext(offs, _lims, ro, _alims, axis_ov,
                                      frozenset(cert), _salts)
                    part_res = cp.executor.run_node(p, e2, ctx)
                    outs.append(self._combine_shard(
                        part_res, p.op, shard, dest_oned[p.dest],
                        _exch.get(p.dest, "psum_scatter"), p.dest))
            return tuple(outs)

        # shard_map's replication check stays on unless a Pallas kernel
        # may run here in interpret mode (off a TPU): the interpreted body
        # mixes unvarying grid indices with varying operands, which the
        # check refuses.  Compiled for a TPU, the kernel passes it.
        from ..kernels import ops as kops
        check = not (kops._interp() and cp.selector.may_pick_pallas())
        fn = jax.jit(jax.shard_map(local_fn, mesh=self.mesh,
                                   in_specs=tuple(in_specs),
                                   out_specs=out_specs, check_vma=check))
        self._round_cache[cache_key] = fn
        self._round_traces += 1
        # traces: executor notes decisions
        results = self._call_round(fn, args, "dist.round_exec", rlabel)
        notes = self._part_notes(node)
        self._round_notes[cache_key] = notes
        self._decisions.update(notes)
        self._strategy_by_key[cache_key] = self._strategy[id(node)]
        pre = {p.dest: env[p.dest] for p in parts}
        self._apply(parts, kinds, results, env)
        self._shard_lost_site(node, rlabel, env, list(zip(dests, kinds)),
                              pre, partial(replay, _fn=fn), rec)

    def _round_desc(self, parts, kinds, axis, exchanges, dest_oned,
                    gathered, local) -> str:
        """The human-readable round strategy explain_rounds() prints —
        shared between single-node rounds and fused-region members so the
        observable format is identical in both paths."""
        desc = []
        for p, k in zip(parts, kinds):
            if k == "reduce":
                x = exchanges[p.dest]
                coll = f"{x.backend}[{x.source}]" if dest_oned[p.dest] \
                    else "psum"
                desc.append(f"reduce({coll})→{p.dest}")
            else:
                desc.append(f"{k}→{p.dest}")   # store/aligned: no collective
        extras = []
        if gathered:
            extras.append("all_gather: " + ",".join(gathered))
        if local:
            extras.append("local blocks: " + ",".join(sorted(local)))
        for p, k in zip(parts, kinds):
            if k == "aligned":   # per-shard contraction: print the static
                cert = shard_slice_certificates(   # bounds certificates
                    p, axis, frozenset(local))
                extras.append(
                    f"slice-certs[{p.dest}]: " + (", ".join(
                        f"{a}={c}" for a, c in sorted(cert.items()))
                        if cert else "none (dense grid)"))
        return (f"{' + '.join(desc)} over {axis}"
                + ("; " + "; ".join(extras) if extras else ""))

    # ------------------- fused regions (pass 11, DESIGN.md §9) -----------
    def _exec_fused(self, region, env, limits, array_limits,
                    loop=None) -> bool:
        """Run a FusedRound region as ONE jit+shard_map program: members
        execute sequentially inside the traced body with their collectives
        (psum / psum_scatter / all_gather) placed between them, instead of
        one shard_map dispatch per member with a host hop in between.
        With `loop`, the member sequence additionally runs under an
        on-device lax.while_loop over the SeqLoop carry — zero host syncs
        for the whole loop.  Returns False when a runtime guard fails
        (member not round-classifiable, §5 packed value, condition not
        computable from replicated state); the caller then falls back to
        per-member rounds / the host-driven loop.  Fusion never changes
        results, only dispatch."""
        from .passes import _expr_names, _scalar_member
        from .tiles import TiledMatrix
        cp = self.cp
        bail_key = id(region) if loop is None else id(loop)
        if bail_key in self._fused_bail:
            return False

        def bail() -> bool:
            self._fused_bail.add(bail_key)
            return False

        # ---- classify members against runtime shapes ----
        units = []
        for m in region.parts:
            if isinstance(m, plan.Rebalance):
                units.append(("rebalance", m, None))
                continue
            spec = self._round_spec(m, env) \
                if (plan.is_reduce(m) or isinstance(m, _STORE_NODES)) \
                else None
            if spec is not None:
                units.append(("round", m, spec))
                continue
            if not _scalar_member(m) or m.space.has_bag or any(
                    jnp.shape(env[d]) != () for d in plan.dests_of(m)):
                return bail()
            units.append(("scalar", m, None))

        # ---- name universe, entry representations ----
        params = cp.program.params
        all_names: set = set()
        bagnames_all: set = set()
        for _k, m, _s in units:
            all_names |= set(m.reads) | set(plan.dests_of(m))
            bagnames_all |= set(m.space.bag_names)
        if loop is not None:
            creads: set = set()
            _expr_names(loop.cond, creads)
            all_names |= {n for n in creads
                          if n in params or n in cp.program.outputs}
        dims = {n: env[n] for n in all_names
                if n in params and params[n].kind == "dim"}
        names = sorted(n for n in all_names if n not in dims)
        if any(isinstance(env[n], TiledMatrix) for n in names):
            return bail()                 # §5 reps cannot cross shard_map
        reps = {}
        for n in names:
            if n in bagnames_all:
                reps[n] = "bag"
            elif self._placed_oned(n):
                reps[n] = "block"
            else:
                reps[n] = "global"
        entry_reps = dict(reps)
        if loop is not None:
            # cond evaluates per shard: every read must be replicated
            for n in creads:
                if n in dims:
                    continue
                if reps.get(n, "global") == "block":
                    return bail()

        # ---- static instruction plan (rep transitions, collectives) ----
        instrs = []
        exchanges_all = {}
        for kind, m, spec in units:
            if kind == "rebalance":
                # active only when the dest is a row-block at this point
                # AND carries a logical limit (else blocks already equal)
                lim = array_limits.get(m.dest)
                active = reps.get(m.dest) == "block" and lim is not None
                instrs.append(("rebalance", m, active, lim))
                continue
            if kind == "scalar":
                reads = sorted(n for n in m.reads if n not in dims)
                g = tuple(n for n in reads if reps.get(n) == "block")
                instrs.append(("scalar", m, g))
                for d in plan.dests_of(m):
                    reps[d] = "global"
                continue
            parts, kinds = spec["parts"], spec["kinds"]
            axis, rng = spec["axis"], spec["rng"]
            member_dests = {p.dest for p in parts}
            reads = sorted(set(m.reads) - member_dests - set(dims))
            bagnames = tuple(m.space.bag_names)
            local_eff = tuple(sorted(
                n for n in spec["local"] if reps.get(n) == "block"))
            gathered = tuple(sorted(
                n for n in reads
                if n not in bagnames and n not in local_eff
                and reps.get(n) == "block"))
            convs = []
            exch = {}
            doned = []
            n_loc = (spec["axis_rows"] or self.dp_n) // self.dp_n
            for p, k in zip(parts, kinds):
                if k == "reduce":
                    shp = jnp.shape(env[p.dest])
                    d_rest = 1
                    for d_ in shp[1:]:
                        d_rest *= int(d_)
                    oned = self._placed_oned(p.dest)
                    exch[p.dest] = cp.selector.choose_exchange(
                        k=int(shp[0]) if shp else 1, d=d_rest, op=p.op,
                        nshards=self.dp_n, n_local=n_loc,
                        dest_dist="ONED_ROW" if oned else "REP")
                    need = "block" if oned else "global"
                else:                     # store/aligned: dest is ONED
                    oned = True
                    need = "block"
                doned.append(oned)
                if reps.get(p.dest, "global") != need:
                    convs.append((p.dest, need))
                reps[p.dest] = need
            exchanges_all.update(exch)
            salts = {}
            for p in parts:
                s = salt_for_node(p, env, cp.selector,
                                  getattr(cp.config, "skew_salting", "auto"),
                                  nshards=self.dp_n, bag_limits=limits)
                if s > 1:
                    salts[p.dest] = s
            instrs.append(("round", m, parts, tuple(kinds), axis, rng,
                           gathered, local_eff, tuple(convs),
                           {d: x.backend for d, x in exch.items()},
                           tuple(doned), bagnames, salts))
        endconvs = []
        if loop is not None:
            # while_loop carries need a stable representation: convert
            # back to the entry rep at body end (normally a no-op)
            for c in loop.carry:
                if reps.get(c) != entry_reps.get(c):
                    endconvs.append((c, entry_reps[c]))
                    reps[c] = entry_reps[c]
        dests_order = []
        for _k, m, _s in units:
            for d in plan.dests_of(m):
                if d not in dests_order:
                    dests_order.append(d)

        # ---- operands, specs, cache key ----
        node_lims = {b: limits[b] for b in sorted(bagnames_all)
                     if b in limits}
        arr_lims = {n: array_limits[n] for n in names if n in array_limits}
        in_specs = []
        args = []
        shapes = {}
        dtypes = {}
        sig = []
        for n in names:
            v = env[n]
            if entry_reps[n] == "bag":
                in_specs.append(tuple(P(self.dp) for _ in v))
                sig.append((n, "bag", tuple(
                    (tuple(c.shape), str(c.dtype)) for c in v)))
            else:
                shapes[n] = tuple(jnp.shape(v))
                dtypes[n] = jnp.asarray(v).dtype
                sig.append((n, entry_reps[n], shapes[n], str(dtypes[n])))
                in_specs.append(P(self.dp) if entry_reps[n] == "block"
                                else P())
            args.append(v)
        out_specs = tuple(P(self.dp) if reps[d] == "block" else P()
                          for d in dests_order)
        def _ikey(i):
            if i[0] == "scalar":
                return (i[0], id(i[1]), i[2])
            if i[0] == "rebalance":
                return (i[0], id(i[1]), i[2], i[3])
            return (i[0], id(i[1]), i[3], i[4], i[5], i[6], i[7], i[8],
                    tuple(sorted(i[9].items())), i[10], i[11],
                    tuple(sorted(i[12].items())))

        cache_key = ("fused", bail_key, tuple(sig),
                     tuple(_ikey(i) for i in instrs),
                     tuple(endconvs), tuple(sorted(node_lims.items())),
                     tuple(sorted(arr_lims.items())),
                     tuple(sorted(dims.items())),
                     tuple(sorted(self._demoted)))
        fn = self._round_cache.get(cache_key)
        if fn is not None:
            self._round_hits += 1
            try:
                results = self._call_round(fn, args, "dist.fused_compile",
                                           "fused")
            except Exception as ex:      # noqa: BLE001 — ladder descent
                # classified descent: the per-member fallback is the next
                # ladder level for a fused region (fusion never changes
                # results, so falling back is always sound)
                self.faults.descend("fused", "per-member rounds", ex)
                return bail()
            self._strategy.update(self._strategy_by_key[cache_key])
            self._decisions.update(self._round_notes[cache_key])
            pre = {d: env[d] for d in dests_order}
            for d, res in zip(dests_order, results):
                env[d] = res
            self._shard_lost_site(
                region, "fused", env,
                [(d, "fused") for d in dests_order], pre,
                lambda _fn=fn, _a=tuple(args):
                    dict(zip(dests_order, _fn(*_a))),
                unit="fused loop" if loop is not None else "fused region")
            return True

        # trace-time: record the region + per-member strategies
        strat = {}
        n_members = len(units)
        head = f"fused round: {n_members} member" + \
            ("s" if n_members != 1 else "") + ", 1 shard_map program"
        if loop is not None:
            head += "; on-device lax.while_loop (0 host syncs)"
            strat[id(loop)] = ("on-device lax.while_loop inside ONE fused "
                               "shard_map round (0 host syncs)")
        strat[id(region)] = head
        for instr in instrs:
            if instr[0] == "scalar":
                strat[id(instr[1])] = "replicated scalar (inside fused round)"
                continue
            if instr[0] == "rebalance":
                _t, m, active, lim = instr
                if active:
                    cts, fac = self._shard_counts(
                        int(jnp.shape(env[m.dest])[0]), lim)
                    strat[id(m)] = (
                        f"rebalance(size-exchange psum + all-to-all "
                        f"psum_scatter)→{m.dest} (inside fused round); "
                        f"rows/shard={cts} balance={fac:.2f}")
                else:
                    strat[id(m)] = ("rebalance: elided ("
                                    + ("already balanced"
                                       if reps.get(m.dest) == "block"
                                       else "replicated dest") + ")")
                continue
            (_t, m, parts, kinds, axis, _rng, gathered, local_eff,
             _convs, exch_b, doned, _bags, _salts) = instr
            strat[id(m)] = self._round_desc(
                parts, kinds, axis, exchanges_all,
                {p.dest: o for p, o in zip(parts, doned)},
                gathered, local_eff)
        self._strategy.update(strat)

        dp, dp_n = self.dp, self.dp_n
        mesh_shape = {a: self.mesh.shape[a] for a in dp}
        carry_names = loop.carry if loop is not None else ()
        cond_expr = loop.cond if loop is not None else None
        dshapes = {d: tuple(jnp.shape(env[d])) for d in dests_order}
        ddtypes = {d: jnp.asarray(env[d]).dtype for d in dests_order}

        def local_fn(*vals):
            e2 = dict(zip(names, vals))
            e2.update(dims)
            shard = 0
            for a in dp:
                shard = shard * mesh_shape[a] + jax.lax.axis_index(a)

            def to_block(v, nme):
                blk = (shapes.get(nme) or dshapes[nme])[0] // dp_n
                return jax.lax.dynamic_slice_in_dim(v, shard * blk, blk,
                                                    axis=0)

            def convert(e, nme, need):
                e[nme] = to_block(e[nme], nme) if need == "block" \
                    else self._gather(e[nme], nme)

            def run_body(e2):
                for instr in instrs:
                    if instr[0] == "rebalance":
                        _t, m, active, lim = instr
                        if active:
                            e2[m.dest] = self._rebalance_local(
                                jnp.asarray(e2[m.dest]), shard, lim)
                        continue
                    if instr[0] == "scalar":
                        _t, m, g = instr
                        eu = dict(e2)
                        for n in g:
                            eu[n] = self._gather(eu[n], n)
                        ctx = ExecContext({}, node_lims, {}, arr_lims, {},
                                          frozenset())
                        e2[m.dest] = cp.executor.run_node(m, eu, ctx)
                        continue
                    (_t, m, parts, kinds, axis, rng, gathered, local_eff,
                     convs, exch, doned, bagnames, salts) = instr
                    for d, need in convs:
                        convert(e2, d, need)
                    eu = dict(e2)
                    for n in gathered:
                        eu[n] = self._gather(eu[n], n)
                    offs = {b: shard * eu[b][0].shape[0] for b in bagnames}
                    row_offs = {n: shard * eu[n].shape[0]
                                for n in local_eff}
                    axis_ov = {}
                    if rng is not None:
                        blk, lim, total = rng
                        axis_ov[axis] = (shard * blk, blk, lim, total)
                    for p, k, oned in zip(parts, kinds, doned):
                        shp, dt = dshapes[p.dest], ddtypes[p.dest]
                        ro = dict(row_offs)
                        cert = set(local_eff)
                        if k == "store":
                            eu[p.dest] = e2[p.dest]
                            ro[p.dest] = shard * eu[p.dest].shape[0]
                            cert.add(p.dest)
                            ctx = ExecContext(offs, node_lims, ro, arr_lims,
                                              axis_ov, frozenset(cert),
                                              salts)
                            e2[p.dest] = cp.executor.run_node(p, eu, ctx)
                        elif k == "aligned":
                            prev = e2[p.dest]
                            blk0 = shp[0] // dp_n
                            eu[p.dest] = jnp.full(
                                (blk0,) + tuple(shp[1:]), identity(p.op, dt))
                            ro[p.dest] = shard * blk0
                            cert.add(p.dest)
                            ctx = ExecContext(offs, node_lims, ro, arr_lims,
                                              axis_ov, frozenset(cert),
                                              salts)
                            res = cp.executor.run_node(p, eu, ctx)
                            e2[p.dest] = COMBINE[p.op](prev, res)
                        else:             # unaligned reduce
                            prev = jnp.asarray(e2[p.dest])
                            eu[p.dest] = jnp.full(shp, identity(p.op, dt))
                            ctx = ExecContext(offs, node_lims, ro, arr_lims,
                                              axis_ov, frozenset(cert),
                                              salts)
                            part_res = cp.executor.run_node(p, eu, ctx)
                            exchd = self._combine_shard(
                                part_res, p.op, shard, oned,
                                exch.get(p.dest, "psum_scatter"), p.dest)
                            e2[p.dest] = COMBINE[p.op](prev, exchd)
                return e2

            if cond_expr is None:
                e2 = run_body(e2)
                return tuple(e2[d] for d in dests_order)

            def cond_fn(c):
                ec = dict(e2)
                ec.update(dict(zip(carry_names, c)))
                return jnp.asarray(cp.executor.eval_scalar(cond_expr, ec),
                                   bool)

            def body_fn(c):
                eb = dict(e2)
                eb.update(dict(zip(carry_names, c)))
                eb = run_body(eb)
                for nme, need in endconvs:
                    convert(eb, nme, need)
                return tuple(jnp.asarray(eb[n]) for n in carry_names)

            carry0 = tuple(jnp.asarray(e2[n]) for n in carry_names)
            out = jax.lax.while_loop(cond_fn, body_fn, carry0)
            e2.update(dict(zip(carry_names, out)))
            return tuple(e2[d] for d in dests_order)

        fn = jax.jit(jax.shard_map(local_fn, mesh=self.mesh,
                                   in_specs=tuple(in_specs),
                                   out_specs=out_specs, check_vma=False))
        try:
            # traces: executor notes decisions
            results = self._call_round(fn, args, "dist.fused_compile",
                                       "fused")
        except Exception as ex:           # noqa: BLE001 — ladder descent
            # a member materialization the fused ctx cannot express, or a
            # classified non-transient fault — guaranteed fallback to
            # per-member rounds, results unchanged
            self.faults.descend("fused", "per-member rounds", ex)
            for k in strat:
                self._strategy.pop(k, None)
            return bail()
        self._round_cache[cache_key] = fn
        self._round_traces += 1
        notes = {}
        for _k, m, _s in units:
            notes.update(self._part_notes(m))
        self._round_notes[cache_key] = notes
        self._decisions.update(notes)
        self._strategy_by_key[cache_key] = strat
        pre = {d: env[d] for d in dests_order}
        for d, res in zip(dests_order, results):
            env[d] = res
        self._shard_lost_site(
            region, "fused", env, [(d, "fused") for d in dests_order], pre,
            lambda _fn=fn, _a=tuple(args): dict(zip(dests_order, _fn(*_a))),
            unit="fused loop" if loop is not None else "fused region")
        return True

    def _part_notes(self, node) -> dict:
        """Snapshot the executor's materialization decisions for the
        node's leaves, as they stand right after this node executed."""
        notes = {}
        parts = node.parts if isinstance(node, plan.Fused) else [node]
        for p in parts:
            d = self.cp.executor.decisions.get(id(p))
            if d is None and isinstance(p, plan.TiledMatmul):
                # dense lhs resolved to the einsum underneath
                d = self.cp.executor.decisions.get(id(p.contract))
            if d is not None:
                notes[id(p)] = d
        return notes

    @staticmethod
    def _apply(parts, kinds, results, env):
        """Fold a round's outputs back into the env: stores replace their
        destination, reductions ⊕-combine with it."""
        for p, k, res in zip(parts, kinds, results):
            if k == "store":
                env[p.dest] = res
            else:
                env[p.dest] = COMBINE[p.op](jnp.asarray(env[p.dest]), res)

    # ------------- surgical shard recovery (DESIGN.md §13) -------------
    def _shard_lost_site(self, node, rlabel, env, writes, pre, replay,
                         rec=None, unit="round"):
        """Fire the post-round shard-loss site (a worker dying while
        holding the partition it just produced) and recover surgically.
        `writes` is [(dest, kind)] for everything the round applied,
        `pre` maps each dest to its pre-apply value (the surviving peer /
        carry-snapshot copy recovery re-fetches), `replay` re-executes the
        round's cached executable and returns {dest: full result}, and
        `rec` (leaf rounds only) carries what a block-restricted host
        recompute of shard k needs."""
        if F.active() is None:
            return                    # zero-cost outside the fault harness
        try:
            F.site("dist.shard_lost", label=rlabel)
        except F.ShardLostFault as ex:
            self._recover_shard(node, rlabel, env, ex, writes, pre,
                                replay, rec, unit)

    def _recover_shard(self, node, rlabel, env, ex, writes, pre, replay,
                       rec, unit):
        """Lineage-based recovery of ONE lost shard partition (DESIGN.md
        §13): replicated destinations cost nothing (every survivor holds
        a full copy); aligned stores / aligned reduces recompute ONLY
        shard k's block from surviving inputs (1/P of the round); sharded
        unaligned reduces and fused regions replay the cached round
        executable and re-slice.  Every recovered block is verified
        against the checksum the peer replica holds (covers the §3.4 mask
        rows too — pad bytes are part of the stamp) before it is spliced
        back.  No ladder descent — unless the same shard was already lost
        within the policy TTL (a flapping worker) or verification fails,
        in which case the original fault re-raises and run()'s ladder
        takes over."""
        lin = getattr(node, "lineage", None)
        k = ex.shard % self.dp_n
        now = self.faults.clock()
        last = self._shard_loss.get(k)
        self._shard_loss[k] = now
        if not self.lineage_enabled or lin is None:
            ex.escalated = True       # pre-§13 behaviour: ladder descent,
            raise ex                  # not a same-level re-dispatch
        if last is not None and (now - last) < self.policy.shard_loss_ttl_s:
            self.faults.record(
                "escalate", rlabel,
                f"shard {k} lost twice within "
                f"{self.policy.shard_loss_ttl_s:.0f}s TTL — flapping "
                f"worker, recomputing onto it again is throwaway; ladder "
                f"takes over")
            ex.escalated = True       # run(): skip same-level re-dispatch
            raise ex
        lost, free = [], []
        for dest, kind in writes:
            if not self._placed_oned(dest):
                free.append(dest)     # survivors hold the full copy
                continue
            v = jnp.asarray(env[dest])
            blk = int(v.shape[0]) // self.dp_n
            start = k * blk
            crc = F.checksum(v[start:start + blk])   # the peer-held stamp
            # the partition died with its worker: poison it so a recovery
            # bug that reads the dead block cannot pass verification
            env[dest] = _kill_block(v, start, blk)
            lost.append((dest, kind, start, blk, crc))
        if not lost:
            self.faults.recovered(
                rlabel,
                f"shard {k}/{self.dp_n}: nothing to recompute — every "
                f"written array is replicated, survivors hold full copies "
                f"(lineage depth={lin.depth})")
            return
        names = ", ".join(f"{d}[{s}:{s + b}]" for d, _k2, s, b, _c in lost)
        blocks = None
        mode = ""
        if rec is not None and all(k2 in ("store", "aligned")
                                   for _d, k2, _s, _b, _c in lost):
            try:
                blocks = self._recompute_blocks(k, pre, env, rec)
            except Exception:         # noqa: BLE001 — fall back to replay
                blocks = None
            if blocks is not None and all(
                    F.checksum(blocks[d]) == c
                    for d, _k2, _s, _b, c in lost):
                mode = (f"block-restricted recompute "
                        f"(1/{self.dp_n} of the round)")
            else:
                blocks = None         # bit mismatch: replay instead
        if blocks is None:
            full = replay()
            blocks = {d: jnp.asarray(full[d])[s:s + b]
                      for d, _k2, s, b, _c in lost}
            if not all(F.checksum(blocks[d]) == c
                       for d, _k2, _s, _b, c in lost):
                self.faults.record(
                    "escalate", rlabel,
                    f"shard {k}: recovered blocks failed peer-checksum "
                    f"verification — ladder takes over")
                ex.escalated = True   # run(): skip same-level re-dispatch
                raise ex
            mode = f"replay {unit} + re-slice"
        for d, _k2, s, b, _c in lost:
            v = jnp.asarray(env[d])
            env[d] = jax.lax.dynamic_update_slice_in_dim(
                v, blocks[d].astype(v.dtype), s, axis=0)
        reads = ", ".join(f"{a}:{k2}" for a, k2 in lin.reads) or "none"
        self.faults.recovered(
            rlabel,
            f"shard {k}/{self.dp_n}: {names} via {mode}; lineage "
            f"depth={lin.depth} (a from-scratch restart would replay "
            f"{lin.depth} round(s)); reads[{reads}]; checksum ok"
            + (f"; free(rep): {','.join(free)}" if free else ""))

    def _recompute_blocks(self, k, pre, env, rec):
        """Host-side mirror of the round's per-shard body for the ONE
        concrete shard k: re-fetch its inputs (replicated arrays are free,
        localized blocks and bag columns are sliced from the surviving
        global copy, gathered reads use the full array any survivor
        already materialized), rebuild the exact ExecContext the dead
        worker ran under, and run the member nodes.  Returns {dest: block}
        for the round's row-block destinations."""
        cp = self.cp
        spec = rec["spec"]
        parts, kinds = spec["parts"], spec["kinds"]
        axis, rng, local = spec["axis"], spec["rng"], spec["local"]
        bagnames = rec["bagnames"]
        e2 = dict(rec["dims"])
        offs, row_offs = {}, {}
        for n in rec["names"]:
            v = env[n]
            if n in bagnames:
                blk_b = int(v[0].shape[0]) // self.dp_n
                e2[n] = tuple(c[k * blk_b:(k + 1) * blk_b] for c in v)
                offs[n] = k * blk_b
            elif n in local:
                blk_n = int(v.shape[0]) // self.dp_n
                e2[n] = v[k * blk_n:(k + 1) * blk_n]
                row_offs[n] = k * blk_n
            else:
                e2[n] = v             # replicated or gathered: full copy
        for d in rec["store_dests"]:  # store operands enter as blocks
            v = jnp.asarray(pre[d])
            blk_d = int(v.shape[0]) // self.dp_n
            e2[d] = v[k * blk_d:(k + 1) * blk_d]
        axis_ov = {}
        if rng is not None:
            blk, lim, total = rng
            axis_ov[axis] = (k * blk, blk, lim, total)
        out = {}
        for p, kind in zip(parts, kinds):
            if not self._placed_oned(p.dest):
                continue
            shp = tuple(jnp.shape(pre[p.dest]))
            dt = jnp.asarray(pre[p.dest]).dtype
            blk0 = shp[0] // self.dp_n
            ro = dict(row_offs)
            cert = set(local)
            e3 = dict(e2)
            ro[p.dest] = k * blk0
            cert.add(p.dest)
            if kind == "store":
                ctx = ExecContext(offs, rec["node_lims"], ro,
                                  rec["arr_lims"], axis_ov,
                                  frozenset(cert), rec["salts"])
                out[p.dest] = cp.executor.run_node(p, e3, ctx)
            elif kind == "aligned":
                prev = jnp.asarray(pre[p.dest])[k * blk0:(k + 1) * blk0]
                e3[p.dest] = jnp.full((blk0,) + tuple(shp[1:]),
                                      identity(p.op, dt))
                ctx = ExecContext(offs, rec["node_lims"], ro,
                                  rec["arr_lims"], axis_ov,
                                  frozenset(cert), rec["salts"])
                res = cp.executor.run_node(p, e3, ctx)
                out[p.dest] = COMBINE[p.op](prev, res)
            else:                     # unaligned reduce: replay instead
                return None
        return out

    # ------------------------- explain -------------------------
    def explain_rounds(self) -> str:
        """Spark-EXPLAIN-style dump of the round strategy chosen for every
        plan node in the LAST run() — aligned store / aligned reduce /
        unaligned reduce (with its collective) / replicated — together with
        the per-shard materialization the executor actually traced for it
        (e.g. ``einsum`` vs ``fallback:dense-grid``).  Classification
        depends on runtime row counts, so call after run()."""
        out = [f"== distributed rounds: {self.cp.program.name} "
               f"({self.dp_n} shards over {self.dp}, mode={self.mode}) =="]
        out.append(f"round cache: {self._round_traces} traced, "
                   f"{self._round_hits} hits")
        if self._demoted:
            out.append("placement: " + ", ".join(
                f"{n}→REP (dest-{d.backend}[{d.source}])"
                for n, d in sorted(self._demoted.items())))
        # skew observability: live rows per shard + max/mean balance factor
        # for every variable-length (ONED_VAR / rebalanced) array
        for n, (cts, fac, kind) in sorted(self._balance.items()):
            out.append(f"balance[{n}]: rows/shard={cts} "
                       f"factor={fac:.2f} ({kind})")
        self._round_lines(self.cp.plan, 0, out)
        return "\n".join(out)

    def explain_faults(self) -> str:
        """The shared per-program failure ledger (one ladder per program,
        whichever layer — distributed or single-device — descended it)."""
        return self.cp.explain_faults()

    def _round_lines(self, nodes, indent, out):
        pre = "  " * indent
        for node in nodes:
            if isinstance(node, plan.SeqLoop):
                out.append(f"{pre}{node.describe()}")
                if id(node) in self._fused_bail:
                    out.append(f"{pre}    bailed: not one fused "
                               f"shard_map round")
                strat = self._strategy.get(id(node))
                if strat is not None:
                    out.append(f"{pre}    loop: {strat}")
                self._round_lines(node.body, indent + 1, out)
                continue
            if isinstance(node, plan.FusedRound):
                out.append(f"{pre}{node.describe()}")
                if id(node) in self._fused_bail:
                    out.append(f"{pre}    bailed: ran as per-member "
                               f"rounds")
                strat = self._strategy.get(id(node))
                if strat is not None:
                    out.append(f"{pre}    round: {strat}")
                self._round_lines(node.parts, indent + 1, out)
                continue
            out.append(f"{pre}{node.describe()}")
            strat = self._strategy.get(id(node))
            if strat is not None:
                out.append(f"{pre}    round: {strat}")
            parts = node.parts if isinstance(node, plan.Fused) else [node]
            for p in parts:
                d = self._decisions.get(id(p))
                if d is not None:
                    out.append(f"{pre}    per-shard[{p.dest}]: {d}")

    # ------------------------- entry -------------------------
    def run(self, inputs: dict) -> dict:
        with _span("repro.run"):
            return self._run(inputs)

    def _run(self, inputs: dict) -> dict:
        """Distributed ladder (DESIGN.md §11/§12): fused → per-member
        rounds (inside _run_once, via _fused_bail) → REP-everything
        placements → the wrapped single-device program, whose own ladder
        ends at the interpreter oracle.  Transients retry at each level
        first; a deterministic error gets exactly ONE descent
        (REP-everything) and surfaces if it reproduces there — it is a
        user error, and the deeper levels would only mask it.

        Capacity errors take a DIFFERENT exit: they must never ascend
        the memory curve.  REP-everything replicates every dense array
        (strictly MORE bytes per device than the sharded placement that
        just OOMed) and single-device concentrates the whole input on
        one device — both rungs are guaranteed re-OOMs.  A classified
        capacity error therefore descends straight to the chunked
        out-of-core tier (core/chunked.py, halving tiles on repeat); with
        out_of_core="off" there is no rung below, and it surfaces."""
        try:
            return F.run_with_retries(
                lambda: self._run_once(inputs),
                policy=self.policy, ledger=self.faults, label="dist")
        except Exception as ex:          # noqa: BLE001 — ladder descent
            if F.classify(ex) == "capacity":
                return self._descend_capacity("rounds", inputs, ex)
            if F.classify(ex) == "shard_lost" \
                    and not getattr(ex, "escalated", False):
                # MID-round loss (the worker died before its outputs
                # applied — nothing to recompute): the program's inputs
                # survive on the host, so ONE same-level re-dispatch
                # re-places them onto the surviving pool before any
                # ladder descent.  Escalated post-round losses (flapping
                # worker, failed verification) skip this and descend.
                try:
                    out = self._run_once(inputs)
                    self.faults.recovered(
                        "dist",
                        "mid-round shard loss: same-level re-dispatch "
                        "onto the surviving pool (inputs survive on the "
                        "host; no round output was lost)")
                    return out
                except Exception as ex2:  # noqa: BLE001 — ladder descent
                    ex = ex2
                    if F.classify(ex) == "capacity":
                        return self._descend_capacity("rounds", inputs, ex)
            self.faults.descend("rounds", "rep", ex)
            if F.classify(ex) == "deterministic":
                out = self._run_once(inputs, force_rep=True)
                self.faults.recover("rep")
                return out
            try:
                out = F.run_with_retries(
                    lambda: self._run_once(inputs, force_rep=True),
                    policy=self.policy, ledger=self.faults, label="rep")
                self.faults.recover("rep")
                return out
            except Exception as ex2:     # noqa: BLE001 — ladder descent
                if F.classify(ex2) == "deterministic":
                    raise
                if F.classify(ex2) == "capacity":
                    return self._descend_capacity("rep", inputs, ex2)
                self.faults.descend("rep", "single-device", ex2)
                out = self.cp.run(inputs)
                self.faults.recover("single-device")
                return out

    def _descend_capacity(self, from_level: str, inputs: dict, ex) -> dict:
        """Capacity exit: down the memory curve (DESIGN.md §12), to the
        chunked tier; with out_of_core="off" the error surfaces at once."""
        if self.cp.out_of_core == "off":
            raise ex
        self.faults.descend(from_level, "chunked", ex)
        return self.cp._run_chunked(inputs, recovering=True)

    def _run_once(self, inputs: dict, force_rep: bool = False) -> dict:
        env = {}
        self._fused_bail = set()     # placements/shapes are per-run
        self._spec_done = set()      # speculation budget is per run
        self._force_rep = force_rep
        try:
            placed, limits, array_limits = self.place(inputs)
        finally:
            self._force_rep = False  # place() consumed it; don't leak
        #                              into direct place() calls (tests)
        for name, t in self.cp.program.params.items():
            v = placed[name]
            if t.kind in ("vector", "matrix", "map"):
                env[name] = jnp.asarray(
                    v, jnp.float32 if t.dtype == "float" else jnp.int32)
            else:
                env[name] = v
        # balance observability for explain_rounds(): the per-shard live
        # row counts every ONED_VAR / rebalanced array holds THIS run
        self._balance = {}
        for name, d in self.dists.items():
            if name in self._rebalanced:
                kind = "rebalance inserted"
            elif d == Dist.ONED_VAR:
                kind = "rebalance elided"
            else:
                continue
            if not self._placed_oned(name):
                continue
            shp = jnp.shape(env[name])
            if not shp:
                continue
            cts, fac = self._shard_counts(int(shp[0]),
                                          array_limits.get(name))
            self._balance[name] = (cts, fac, kind)
        self._exec_shardmap(self.cp.plan, env, limits, array_limits)
        out = {}
        for n in self.cp.program.outputs:
            v = env[n]
            lim = array_limits.get(n)
            out[n] = v if lim is None else v[:lim]   # drop pad rows
        return out


def _kill_block(v, start, blk):
    """Destroy rows [start, start+blk): the partition died with its
    worker.  Poisoned with NaN / an integer sentinel rather than left
    stale so that any recovery path reading the dead block fails the
    peer-checksum verification instead of silently passing."""
    if jnp.issubdtype(v.dtype, jnp.floating):
        fill = jnp.nan
    elif jnp.issubdtype(v.dtype, jnp.integer):
        fill = jnp.iinfo(v.dtype).min
    else:
        fill = 0
    dead = jnp.full((blk,) + tuple(v.shape[1:]), fill, v.dtype)
    return jax.lax.dynamic_update_slice_in_dim(v, dead, start, axis=0)


def _gather_names(node) -> frozenset:
    from .dist_analysis import gathers_of
    return frozenset(gathers_of(node))


def _walk_plan(nodes):
    """Every leaf plan node, containers opened (SeqLoop bodies, FusedRound
    regions, Fused parts)."""
    for n in nodes:
        if isinstance(n, plan.SeqLoop):
            yield from _walk_plan(n.body)
        elif isinstance(n, plan.FusedRound):
            yield from _walk_plan(n.parts)
        elif isinstance(n, plan.Fused):
            yield from n.parts
        else:
            yield n


def compile_distributed(fn_or_prog, mesh, dp_axes=("data",),
                        mode: str = "shardmap", shard_dense: bool = True,
                        **kw) -> DistributedProgram:
    from .lower import compile_program
    cp = fn_or_prog if isinstance(fn_or_prog, CompiledProgram) \
        else compile_program(fn_or_prog, **kw)
    return DistributedProgram(cp, mesh, dp_axes, mode, shard_dense)
