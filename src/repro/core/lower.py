"""Plan execution: physical-plan nodes → JAX.

The pipeline is  translate (Fig. 2) → passes.plan_program (operator
recognition, see passes.py) → PlanExecutor (this module).  The executor
performs NO recognition: every operator choice was made by the pass
pipeline; this module only materializes the chosen node, checking the
runtime guards (extents, packed-vs-dense inputs) that static planning
cannot see.  When a guard fails the executor walks the node's `fallback`
chain — results never change, only the operator used.

Node → JAX mapping:

  MapExpr         broadcasted value over the iteration space; full replace
                  or meshgrid .at[].set with drop semantics
  DenseMap        dense fast path: ONE vectorized jnp expression over whole
                  arrays / per-shard blocks — no index grids, gathers,
                  masks or scatters (guard: extents cover the destination
                  exactly) — else the general MapExpr path
  Scatter         .at[].set at computed keys, OOB rows dropped
  SegmentReduce   one of four backends, chosen at trace time by the
                  operator-selection subsystem (op_select.py, DESIGN.md
                  §8) from the node's candidate set: native scatter-⊕
                  with drop semantics (no identity segment array, no
                  index flattening), sort-based jax.ops.segment_⊕ over
                  sorted keys, one-hot dot_general on the MXU, or the
                  Pallas blocked one-hot kernel.  `backend="auto"`
                  resolves via the cost model / autotune cache against
                  the concrete (N, K, D, dtype, dest-sharding) shape
                  class; a concrete backend name pins the choice.  The
                  resolved decision is recorded (explain() prints it)
  AxisReduce      ⊕-reduce over contracted axes (Rule 17: no shuffle); a
                  `product` certificate contracts via jnp.einsum instead of
                  the dense grid (same operator, MXU materialization)
  EinsumContract  jnp.einsum over sliced operands (guard: offsets static
                  OR certified per-shard — aligned local blocks slice at
                  0, replicated operands via bounds-proven dynamic_slice;
                  pad limits only on the leading key axis) — else its
                  AxisReduce fallback
  TiledMatmul     block-sparse Pallas tile_matmul on the §5 packed lhs
                  (guard: lhs arrives as TiledMatrix) — else einsum
  ScalarReduce    total ⊕-reduce (+ any/all peephole for max/min of
                  float(bool)); `point` targets one destination cell
  SeqLoop         lax.while_loop over the mutated-variable carry
  Fused           parts executed against the shared iteration space

Distributed execution passes bag offsets/limits through ExecContext — plan
parameters, not executor state — so the same plan serves single-device,
shard_map and gspmd backends (see distributed.py).

The compiled program is a pure function dict->dict and is jit-compatible
(dims must be python ints: they define static shapes).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from . import faults as F
from . import plan as P
from .analysis import check as check_restrictions
from ..precision import xla_f32_dot
from .comprehension import Get, pretty
from .loop_ast import (BinOp, Call, Const, Program, RejectionError, UnOp,
                       Var)
from .passes import PlanConfig, plan_program
from .translate import translate


# ---------------------------------------------------------------------------
# scalar op tables (public: distributed.py composes partials with these)
# ---------------------------------------------------------------------------

OPS = {
    "+": jnp.add, "-": jnp.subtract, "*": jnp.multiply, "/": jnp.divide,
    "//": jnp.floor_divide, "%": jnp.mod, "**": jnp.power,
    "==": jnp.equal, "!=": jnp.not_equal, "<": jnp.less,
    "<=": jnp.less_equal, ">": jnp.greater, ">=": jnp.greater_equal,
    "and": jnp.logical_and, "or": jnp.logical_or,
}

FNS = {"sqrt": jnp.sqrt, "exp": jnp.exp, "log": jnp.log, "abs": jnp.abs,
       "sin": jnp.sin, "cos": jnp.cos, "tanh": jnp.tanh,
       "sigmoid": jax.nn.sigmoid, "float": lambda x: jnp.asarray(x, jnp.float32),
       "int": lambda x: jnp.asarray(x, jnp.int32),
       "min": jnp.minimum, "max": jnp.maximum,
       "where": lambda c, a, b: jnp.where(c, a, b)}

REDUCE = {"+": jnp.sum, "*": jnp.prod, "min": jnp.min, "max": jnp.max}
COMBINE = {"+": jnp.add, "*": jnp.multiply, "min": jnp.minimum,
           "max": jnp.maximum}


def _operands(e) -> tuple:
    """The operand expressions of a BinOp, UnOp or Call."""
    if isinstance(e, BinOp):
        return (e.lhs, e.rhs)
    return (e.e,) if isinstance(e, UnOp) else tuple(e.args)


def _apply(e, vals):
    """A BinOp, UnOp or Call applied to its evaluated operands."""
    if isinstance(e, BinOp):
        return OPS[e.op](*vals)
    if isinstance(e, UnOp):
        return -vals[0] if e.op == "neg" else jnp.logical_not(vals[0])
    return FNS[e.fn](*vals)


def _premap_leaves(e, env, binding, gathers: list) -> bool:
    """True when `e` is built of BinOp / UnOp / Call over constants,
    scalars not bound to the iteration space and gathers, which it
    appends to `gathers`."""
    if isinstance(e, Const):
        return True
    if isinstance(e, Var):
        return e.name not in binding and jnp.ndim(env[e.name]) == 0
    if isinstance(e, (P.Gather, Get)):
        gathers.append(e)
        return True
    return isinstance(e, (BinOp, UnOp, Call)) and all(
        _premap_leaves(a, env, binding, gathers) for a in _operands(e))


def _table(e, env):
    """`e` over whole arrays: each gather stands for its array."""
    if isinstance(e, (P.Gather, Get)):
        return env[e.array]
    if isinstance(e, (BinOp, UnOp, Call)):
        return _apply(e, [_table(a, env) for a in _operands(e)])
    return jnp.asarray(env[e.name] if isinstance(e, Var) else e.value)


def _same(a, b) -> bool:
    """Two row offsets or limits that are provably equal at trace time:
    both absent, one traced value, or equal ints."""
    return a is b or (isinstance(a, (int, np.integer))
                      and isinstance(b, (int, np.integer)) and a == b)


# host spans of run() (repro.run and its children, README "Tracing a
# job"): written into a running profiler's trace on the device trace's
# clock.  Constant names and no metadata, so with no profiler running a
# span costs about a microsecond
_span = jax.profiler.TraceAnnotation


def identity(op: str, dtype) -> jnp.ndarray:
    """The ⊕ identity element for masked-out rows."""
    if op == "+":
        return jnp.zeros((), dtype)
    if op == "*":
        return jnp.ones((), dtype)
    big = jnp.asarray(np.inf, dtype) if jnp.issubdtype(dtype, jnp.floating) \
        else jnp.asarray(jnp.iinfo(dtype).max, dtype)
    return -big if op == "max" else big


def _scatter_op(ref, op: str):
    return {"+": ref.add, "*": ref.multiply, "min": ref.min, "max": ref.max}[op]


class Axes:
    """Materialized iteration space: ordered axes with concrete extents."""

    def __init__(self):
        self.order: list[str] = []
        self.extent: dict[str, int] = {}

    def add(self, name: str, n: int):
        self.order.append(name)
        self.extent[name] = n

    def pos(self, name: str) -> int:
        return self.order.index(name)

    def shape(self):
        return tuple(self.extent[a] for a in self.order)

    def expand(self, arr, axis_name: str):
        """1-D array along `axis_name` → broadcast rank."""
        shape = [1] * len(self.order)
        shape[self.pos(axis_name)] = -1
        return jnp.reshape(arr, shape)


@dataclass(frozen=True)
class ExecContext:
    """Per-call plan parameters for distributed execution: traced global
    index offsets for sharded bags, and logical bag lengths when columns
    were padded to a multiple of the shard count.

    The dense-array analogues (distribution analysis, DESIGN.md §6) reuse
    the same machinery for ONED_ROW arrays:

      row_offsets     array → traced global row index of the local block's
                      first row; the executor subtracts it so dim-0 reads
                      and writes of the array target the per-shard block
      array_limits    array → logical dim-0 length when rows were padded
                      to a multiple of the shard count; reads at global
                      row ≥ limit are masked and writes dropped, so pad
                      rows can never change a result (paper §3.4 empty-bag
                      semantics against the LOGICAL bound)
      axis_overrides  range-axis var → (offset, extent, limit, total): the
                      round localizes the axis to the shard's row block
                      exactly like a sharded bag axis (offset globalizes
                      the index var, rows beyond `limit` are masked out).
                      `total` is the STATIC padded global extent
                      (shards × extent): the bounds certificate for slicing
                      a replicated operand per shard — offset + extent ≤
                      total always, so when total ≤ the operand's physical
                      dim a lax.dynamic_slice can never clamp (DESIGN.md
                      §7).
      aligned         alignment certificates: names whose dim-0 LOCAL
                      block is exactly the round axis' override window
                      ([offset, offset+extent)).  distributed.py issues
                      one only when the distribution analysis proved every
                      read leading-indexed by the round axis AND the
                      physical rows tile exactly like the axis, so the
                      executor may treat the traced window start as a
                      static local 0.
      salts           group-by dest → salt factor S resolved by the
                      RUN-TIME hot-key probe (op_select.probe_hot_fraction
                      + choose_salt) for this call's concrete key data.
                      The executor spreads each key over S sub-
                      destinations (`salt*K + key`) and ⊕-folds the [S, K]
                      partial back, so skewed keys stop serializing the
                      scatter.  Static pins (`SegmentReduce.salt`, set by
                      the planner from `PlanConfig.skew_salting`) take
                      precedence; callers put the resolved dict in their
                      compile-cache key, since the decision changes the
                      traced computation.
    """
    bag_offsets: dict = field(default_factory=dict)
    bag_limits: dict = field(default_factory=dict)
    row_offsets: dict = field(default_factory=dict)
    array_limits: dict = field(default_factory=dict)
    axis_overrides: dict = field(default_factory=dict)
    aligned: frozenset = frozenset()
    salts: dict = field(default_factory=dict)


_EMPTY_CTX = ExecContext()


def salt_for_node(node, env, selector, skew_salting: str, *,
                  nshards: int = 1, bag_limits=None) -> int:
    """Run-time half of the hot-key salting decision for one group-by
    node: probe the CONCRETE key column host-side and ask the selector's
    cost model / cache for the salt factor (1 = do not salt).  Only fires
    in "auto" mode on nodes without a static pin, and only for the probe-
    able shape — a single key that IS a bag column (the word-count /
    group-by form), reduced into a 1-D destination.  Everything else keeps
    S=1: salting is an optimization, never a requirement."""
    if not isinstance(node, P.SegmentReduce) or node.salt is not None \
            or skew_salting != "auto":
        return 1
    if len(node.keys) != 1 or not isinstance(node.keys[0], Var):
        return 1
    dest = env.get(node.dest)
    if dest is None or len(jnp.shape(dest)) != 1:
        return 1
    kv = node.keys[0].name
    bag, col = None, 0
    for a in node.space.axes:
        if a.kind == "bag" and kv in a.vals:
            bag, col = a.bag, a.vals.index(kv)
            break
    if bag is None or bag not in env:
        return 1
    bv = env[bag]
    c = (bv if isinstance(bv, tuple) else (bv,))[col]
    if isinstance(c, jax.core.Tracer):
        return 1                  # under an outer trace: no concrete data
    n = int(c.shape[0])
    lim = (bag_limits or {}).get(bag)
    if lim is not None:
        n = min(n, int(lim))
    if n == 0:
        return 1
    from .op_select import probe_hot_fraction
    hot = probe_hot_fraction(np.asarray(c[:min(n, 4096)]))
    dec = selector.choose_salt(n=n, k=int(jnp.shape(dest)[0]), op=node.op,
                               nshards=nshards, hot_frac=hot)
    return int(dec.backend.split(":", 1)[1]) \
        if dec.backend.startswith("salt:") else 1


def collect_salts(nodes, env, selector, skew_salting: str, *,
                  nshards: int = 1, bag_limits=None) -> dict:
    """dest → salt factor for every probe-decided group-by in the plan
    (walks SeqLoop bodies and fused regions).  Callers thread the result
    through ExecContext.salts AND their compile-cache key — the factor is
    baked into the trace."""
    out: dict = {}
    def walk(ns):
        for n in ns:
            if isinstance(n, P.SeqLoop):
                walk(n.body)
            elif isinstance(n, (P.Fused, P.FusedRound)):
                walk(n.parts)
            else:
                s = salt_for_node(n, env, selector, skew_salting,
                                  nshards=nshards, bag_limits=bag_limits)
                if s > 1:
                    out[n.dest] = s
    walk(nodes)
    return out


# ---------------------------------------------------------------------------
# plan executor
# ---------------------------------------------------------------------------

class PlanExecutor:
    def __init__(self, prog: Program, selector=None):
        self.prog = prog
        # id(node) → the materialization the executor last chose for it
        # ("einsum", "mxu-einsum", "dense-store", "segment:scatter[cost]",
        # …).  Written at trace time; CompiledProgram.explain() and
        # DistributedProgram.explain_rounds() read it to report the ACTUAL
        # operator/backend of each compiled node or per-shard round.
        self.decisions: dict = {}
        self._selector = selector
        # group-by values read through one gather of a map (`_premap`),
        # counted at trace time
        self.premaps = 0

    @property
    def selector(self):
        if self._selector is None:
            from .op_select import OpSelector
            self._selector = OpSelector()
        return self._selector

    def note(self, node, tag: str) -> None:
        self.decisions[id(node)] = tag

    # ---- static scalars (dims / range bounds) ----
    def static_int(self, e, env) -> int:
        if isinstance(e, Const):
            return int(e.value)
        if isinstance(e, Var):
            v = env[e.name]
            if isinstance(v, (int, np.integer)):
                return int(v)
            raise RejectionError(
                f"range bound '{e.name}' must be a static dim (python int)")
        if isinstance(e, BinOp):
            l = self.static_int(e.lhs, env)
            r = self.static_int(e.rhs, env)
            return int({"+": l + r, "-": l - r, "*": l * r,
                        "//": l // r, "/": l // r}[e.op])
        raise RejectionError(f"non-static range bound {e}")

    # ---- materialize an IterSpace against the env ----
    def build_space(self, space: P.IterSpace, env, ctx: ExecContext):
        ax = Axes()
        binding: dict[str, tuple] = {}  # var -> ("range", axis, lo)|("bagval", axis, col)
        for a in space.axes:
            if a.kind == "range":
                ov = ctx.axis_overrides.get(a.var)
                if ov is not None:      # localized to the shard's row block
                    off, ext, _lim, _tot = ov
                    ax.add(a.var, ext)
                    binding[a.var] = ("range", a.var, off)
                    continue
                lo = self.static_int(a.lo, env)
                hi = self.static_int(a.hi, env)
                ax.add(a.var, max(hi - lo, 0))
                binding[a.var] = ("range", a.var, lo)
            else:
                bagv = env[a.bag]
                cols = bagv if isinstance(bagv, tuple) else (bagv,)
                n = int(cols[0].shape[0])
                ax.add(a.var, n)
                binding[a.var] = ("range", a.var,
                                  ctx.bag_offsets.get(a.bag, 0))
        base_masks = []
        for a in space.axes:
            if a.kind == "range":
                ov = ctx.axis_overrides.get(a.var)
                if ov is not None and ov[2] is not None:
                    off, ext, lim, _tot = ov  # mask rows ≥ the logical extent
                    base_masks.append(ax.expand(
                        (off + jnp.arange(ext)) < lim, a.var))
                continue
            bagv = env[a.bag]
            cols = bagv if isinstance(bagv, tuple) else (bagv,)
            for j, v in enumerate(a.vals):
                binding[v] = ("bagval", a.var, cols[j])
            lim = ctx.bag_limits.get(a.bag)
            if lim is not None:
                off = binding[a.var][2]
                base_masks.append(ax.expand(
                    (off + jnp.arange(ax.extent[a.var])) < lim, a.var))
        return ax, binding, list(space.conds), base_masks

    # ---- expression evaluation over the iteration space ----
    def eval(self, e, env, ax: Axes, binding, masks: list,
             ctx: ExecContext = _EMPTY_CTX, premap: list | None = None):
        """Value of `e` over the iteration space; every gather appends its
        inRange masks to `masks`.  With `premap` (a list: a group-by's
        value), a subexpression whose gathers share one index is read as
        one gather of its map over the arrays (`_premap`), and the names
        of those arrays are appended to the list."""
        if isinstance(e, Const):
            return jnp.asarray(e.value)
        if isinstance(e, Var):
            if e.name in binding:
                kind, axis, aux = binding[e.name]
                if kind == "range":
                    return ax.expand(aux + jnp.arange(ax.extent[axis]), axis)
                return ax.expand(aux, axis)
            return jnp.asarray(env[e.name])
        if isinstance(e, (P.Gather, Get)):
            arr = env[e.array]
            from .tiles import TiledMatrix, unpack
            if isinstance(arr, TiledMatrix):   # §5 fallback: unpack on read
                arr = unpack(arr)
            if self._broadcasts(e, arr, ax, binding, ctx):
                names = [ix.name for ix in e.idxs]
                shape = [1] * len(ax.order)
                perm_src = sorted(names, key=ax.pos)
                a2 = jnp.transpose(arr, [names.index(a) for a in perm_src])
                for a in perm_src:
                    shape[ax.pos(a)] = ax.extent[a]
                return jnp.reshape(a2, shape)
            idxs = [self.eval(i, env, ax, binding, masks, ctx)
                    for i in e.idxs]
            return self._gather(arr, e.array, idxs, masks, ctx)
        if isinstance(e, (BinOp, UnOp, Call)):
            if premap is not None:
                v = self._premap(e, env, ax, binding, masks, ctx, premap)
                if v is not None:
                    return v
            return _apply(e, [self.eval(a, env, ax, binding, masks, ctx,
                                        premap) for a in _operands(e)])
        raise RejectionError(f"cannot execute expression {e}")

    def _broadcasts(self, e, arr, ax, binding, ctx) -> bool:
        """Identity-traversal broadcast: statically marked eligible, and
        the runtime extents cover the array exactly (no gather).  Padded
        (array_limits) and localized (row_offsets) arrays never qualify:
        their extents differ from the physical dim."""
        bc_ok = e.broadcast_ok if isinstance(e, P.Gather) else True
        return bc_ok and len(e.idxs) == len(arr.shape) and \
            e.array not in ctx.row_offsets and \
            e.array not in ctx.array_limits and \
            all(isinstance(ix, Var) and ix.name in binding
                and binding[ix.name][0] == "range"
                and isinstance(binding[ix.name][2], int)
                and binding[ix.name][2] == 0
                and ax.extent[ix.name] == d
                for ix, d in zip(e.idxs, arr.shape)) and \
            len({ix.name for ix in e.idxs}) == len(e.idxs)

    def _gather(self, arr, name, idxs, masks, ctx):
        """`arr` read at the evaluated indices `idxs`, with the array
        `name`'s row offset and logical limit; appends the inRange masks."""
        off = ctx.row_offsets.get(name)
        lim = ctx.array_limits.get(name)
        cooked = []
        for dim_i, (d, ix) in enumerate(zip(arr.shape, idxs)):
            ix = jnp.asarray(ix, jnp.int32)
            if dim_i == 0:
                if lim is not None:     # logical bound, global coords
                    masks.append(ix < lim)
                if off is not None:     # localize to the shard's block
                    ix = ix - off
            # uint32 reinterpretation: negatives wrap past any dim, so
            # ONE unsigned compare is the whole inRange check
            # ((ix >= 0) & (ix < d)) and the gather indexes unsigned
            iu = ix.astype(jnp.uint32)
            masks.append(iu < jnp.uint32(d))
            cooked.append(iu)
        # clip-mode gather on the unsigned indices: out-of-range rows
        # read a clamped row, and §3.4 empty-bag semantics live in the
        # recorded inRange MASK, which every consumer applies — the
        # gathered value at a dropped row is never observable.  Clamp
        # is one fusable op; a fill-mode gather would add a
        # compare+select pair per gather, measured ~20% slower on the
        # scatter-fed group-by path (pagerank's inner loop).
        if len(cooked) == 1:
            return jnp.take(arr, cooked[0], axis=0, mode="clip")
        return arr.at[tuple(jnp.broadcast_arrays(*cooked))].get(
            mode="clip")

    def _premap(self, e, env, ax, binding, masks, ctx, premapped: list):
        """Gathers that share one index, read as one gather of a map:
        `A[i] / B[i]` as `(A / B)[i]`.  `e` qualifies when it is built of
        BinOp / UnOp / Call over constants, scalars (names not bound to
        the iteration space) and gathers of at least two distinct arrays,
        all at one index tuple; the arrays have one shape, the same row
        offset and logical limit, none arrives §5-packed and none is read
        by identity broadcast.  Each row then applies the same elementwise
        ops to the same values, bit for bit, and a clamped or masked row
        reads one row of every array either way.  The map is evaluated
        once over the arrays (the table, scope `premap`) and gathered with
        one set of indices and inRange masks.  Only where the table holds
        no more elements than the index has rows: a chunk of rows, or a
        small call, keeps one gather per array.  None when `e` does not
        qualify."""
        from .tiles import TiledMatrix
        gathers: list = []
        if not _premap_leaves(e, env, binding, gathers):
            return None
        names = list(dict.fromkeys(g.array for g in gathers))
        idxs = gathers[0].idxs if gathers else ()
        if len(names) < 2 or any(g.idxs != idxs for g in gathers):
            return None
        arrs = [env[n] for n in names]
        if any(isinstance(a, TiledMatrix) for a in arrs) or any(
                self._broadcasts(g, env[g.array], ax, binding, ctx)
                for g in gathers):
            return None
        shape = jnp.shape(arrs[0])
        if any(jnp.shape(a) != shape
               or not _same(ctx.row_offsets.get(n),
                            ctx.row_offsets.get(names[0]))
               or not _same(ctx.array_limits.get(n),
                            ctx.array_limits.get(names[0]))
               for n, a in zip(names, arrs)):
            return None
        ix_masks: list = []
        ix = [self.eval(i, env, ax, binding, ix_masks, ctx) for i in idxs]
        rows = int(np.prod(jnp.broadcast_shapes(*[jnp.shape(i)
                                                  for i in ix])))
        if int(np.prod(shape)) > rows:
            return None
        masks.extend(ix_masks)
        with jax.named_scope("premap"):
            table = _table(e, env)
        self.premaps += 1
        premapped.extend(names)
        return self._gather(table, names[0], ix, masks, ctx)

    def _mask(self, conds, env, ax, binding, masks,
              ctx: ExecContext = _EMPTY_CTX):
        for c in conds:
            masks.append(self.eval(c, env, ax, binding, masks, ctx))
        uniq: list = []                  # repeated reads of one array CSE
        for x in masks:                  # to one traced mask: AND it once
            if not any(x is u for u in uniq):
                uniq.append(x)
        if not uniq:
            return None
        m = uniq[0]
        for x in uniq[1:]:
            m = jnp.logical_and(m, x)
        return jnp.broadcast_to(m, ax.shape()) if ax.order else m

    # ------------------------------------------------------------------
    # node execution.  run_node returns the NEW VALUE of each destination
    # (a tuple for Fused); execute() assigns them into the env.
    # ------------------------------------------------------------------

    def execute(self, nodes, env, ctx: ExecContext = _EMPTY_CTX):
        for node in nodes:
            if isinstance(node, P.SeqLoop):
                if node.cond is None and getattr(node, "chunk_bag", None):
                    # a ChunkLoop (core/chunked.py) reaching the plain
                    # executor: the whole bag is resident here, so the
                    # stream degrades to one all-resident "tile" — plain
                    # sequencing of the body, same results
                    self.execute(node.body, env, ctx)
                    continue
                with jax.named_scope("SeqLoop"):
                    self._exec_seq_loop(node, env, ctx)
            elif isinstance(node, P.FusedRound):
                # round-fusion region: plain sequencing on a single device
                # (the grouping only matters to the distributed executor)
                self.execute(node.parts, env, ctx)
            elif isinstance(node, P.Fused):
                for part, v in zip(node.parts, self.run_node(node, env, ctx)):
                    env[part.dest] = v
            else:
                env[node.dest] = self.run_node(node, env, ctx)

    def run_node(self, node, env, ctx: ExecContext = _EMPTY_CTX):
        # every op the node lowers to carries the named scope
        # `<NodeType>:<dest>` (a Fused node's dests joined by "+") in its
        # HLO metadata, which the device trace reports per op.  Trace
        # time only: the compiled program runs no differently
        with jax.named_scope(
                f"{type(node).__name__}:{'+'.join(P.dests_of(node))}"):
            return self._run_node(node, env, ctx)

    def _run_node(self, node, env, ctx: ExecContext):
        # per-node guard site (DESIGN.md §11): under jit this fires at
        # trace time — a fault here fails the whole-program trace, whose
        # ladder then descends to the eager path where it fires again
        F.site("lower.node", node=type(node).__name__)
        if isinstance(node, P.Rebalance):
            # single device: one shard holds every row, blocks are balanced
            # by construction — the round is the identity (the distributed
            # executor runs the real size-exchange + all-to-all)
            self.note(node, "rebalance:noop[single-device]")
            return env[node.dest]
        if isinstance(node, P.DenseMap):
            res = self._exec_dense_map(node, env, ctx)
            if res is not None:
                return res
            self.note(node, "fallback:general-store")
            return self._exec_map(node, env, ctx)
        if isinstance(node, P.MapExpr):
            return self._exec_map(node, env, ctx)
        if isinstance(node, P.Scatter):
            return self._exec_scatter(node, env, ctx)
        if isinstance(node, P.SegmentReduce):
            return self._exec_segment(node, env, ctx)
        if isinstance(node, P.AxisReduce):
            return self._exec_axis_reduce(node, env, ctx)
        if isinstance(node, P.EinsumContract):
            return self._exec_einsum(node, env, ctx)
        if isinstance(node, P.TiledMatmul):
            return self._exec_tiled(node, env, ctx)
        if isinstance(node, P.ScalarReduce):
            return self._exec_scalar_reduce(node, env, ctx)
        if isinstance(node, P.Fused):
            return tuple(self.run_node(p, env, ctx) for p in node.parts)
        raise RejectionError(f"cannot execute plan node {node}")

    # ---- stores ----
    def _eval_dense(self, e, key_axes, ax, binding, env, ctx):
        """Whole-array evaluation of a dense-fastpath value: identity
        gathers resolve to the operand (sliced per shard under the bounds
        certificates of `_sliced_operand`), scalars broadcast.  None when a
        guard fails (caller takes the general grid path)."""
        if isinstance(e, Const):
            return jnp.asarray(e.value)
        if isinstance(e, Var):
            return jnp.asarray(env[e.name])
        if isinstance(e, (P.Gather, Get)):
            arr = env[e.array]
            from .tiles import TiledMatrix, unpack
            if isinstance(arr, TiledMatrix):
                arr = unpack(arr)
            if len(arr.shape) != len(key_axes):
                return None
            # pad_ok=False: a store must DROP out-of-range writes (keep the
            # old destination), which zero-padding cannot emulate
            return self._sliced_operand(arr, e.array, key_axes, ax,
                                        binding, ctx, pad_ok=False)
        if isinstance(e, BinOp):
            lhs = self._eval_dense(e.lhs, key_axes, ax, binding, env, ctx)
            rhs = self._eval_dense(e.rhs, key_axes, ax, binding, env, ctx)
            if lhs is None or rhs is None:
                return None
            return OPS[e.op](lhs, rhs)
        if isinstance(e, UnOp):
            v = self._eval_dense(e.e, key_axes, ax, binding, env, ctx)
            if v is None:
                return None
            return -v if e.op == "neg" else jnp.logical_not(v)
        if isinstance(e, Call):
            args = [self._eval_dense(a, key_axes, ax, binding, env, ctx)
                    for a in e.args]
            if any(a is None for a in args):
                return None
            return FNS[e.fn](*args)
        return None

    def _exec_dense_map(self, node: P.DenseMap, env, ctx):
        """DenseMap fast path: the pass proved identity indexing (keys =
        axes, identity gathers only, no conditions); verify at runtime
        that the extents cover the destination exactly, then emit ONE
        vectorized jnp expression — no index grids, no gather/scatter, no
        masks.  Per shard, aligned operands are their local blocks and
        replicated ones a bounds-certified dynamic slice; rows beyond the
        logical limit keep the destination's (zero) pad values.  Returns
        None when a guard fails (caller: general MapExpr path)."""
        from .tiles import TiledMatrix
        dest = env[node.dest]
        if isinstance(dest, TiledMatrix):
            return None
        ax, binding, conds, base = self.build_space(node.space, env, ctx)
        lim = None
        for pos, a in enumerate(node.space.axes):
            ov = ctx.axis_overrides.get(a.var)
            if ov is not None:
                if pos != 0:     # only the round axis may be localized
                    return None
                lim = ov[2]
        if tuple(ax.shape()) != tuple(dest.shape):
            return None          # space must cover the dest exactly
        if ctx.array_limits.get(node.dest) is not None \
                and node.dest not in ctx.aligned:
            return None          # padded global dest needs the drop path
        val = self._eval_dense(node.value, node.key_axes, ax, binding, env,
                               ctx)
        if val is None:
            return None
        val = jnp.broadcast_to(jnp.asarray(val), ax.shape())
        val = val.astype(dest.dtype)
        if lim is not None:      # keep (zero) pad rows beyond the limit
            ov = ctx.axis_overrides[node.space.axes[0].var]
            keep = (ov[0] + jnp.arange(ov[1])) < lim
            keep = keep.reshape((-1,) + (1,) * (val.ndim - 1))
            val = jnp.where(keep, val, dest)
        self.note(node, "dense-store")
        return val

    def _exec_map(self, node: P.MapExpr, env, ctx):
        ax, binding, conds, base = self.build_space(node.space, env, ctx)
        if node.key_axes is None:          # guarded scalar assignment
            masks = list(base)
            val = self.eval(node.value, env, ax, binding, masks, ctx)
            m = self._mask(conds, env, ax, binding, masks, ctx)
            if m is not None:
                old = env.get(node.dest, jnp.zeros_like(val))
                return jnp.where(m, val, old)
            return val

        dest = env[node.dest]
        masks = list(base)
        val = self.eval(node.value, env, ax, binding, masks, ctx)
        m = self._mask(conds, env, ax, binding, masks, ctx)
        key_axes = node.key_axes
        val = jnp.broadcast_to(val, ax.shape())
        perm = [ax.order.index(a) for a in key_axes]
        val = jnp.transpose(val, perm)
        if m is not None:
            m = jnp.transpose(jnp.broadcast_to(m, ax.shape()), perm)
        los = [binding[a][2] for a in key_axes]
        exts = [ax.extent[a] for a in key_axes]
        dest_off = ctx.row_offsets.get(node.dest)
        dest_lim = ctx.array_limits.get(node.dest)
        static0 = all(isinstance(l, int) and l == 0 for l in los)
        if tuple(exts) == dest.shape and static0 and m is None \
                and dest_lim is None:
            return val.astype(dest.dtype)                 # full replace
        grids = list(jnp.meshgrid(
            *[los[i] + jnp.arange(exts[i]) for i in range(len(exts))],
            indexing="ij"))
        keep = m
        if dest_lim is not None:          # pad rows: drop (logical bound)
            ok = grids[0] < dest_lim
            keep = ok if keep is None else (keep & ok)
        if dest_off is not None:          # localize rows to the shard block
            grids[0] = grids[0] - dest_off
        if keep is not None:
            grids[0] = jnp.where(keep, grids[0], dest.shape[0])  # drop
        return dest.at[tuple(grids)].set(val.astype(dest.dtype), mode="drop")

    def _exec_scatter(self, node: P.Scatter, env, ctx):
        ax, binding, conds, base = self.build_space(node.space, env, ctx)
        dest = env[node.dest]
        masks = list(base)
        val = self.eval(node.value, env, ax, binding, masks, ctx)
        m = self._mask(conds, env, ax, binding, masks, ctx)
        shape = ax.shape()
        val = jnp.broadcast_to(val, shape)
        kk = [jnp.broadcast_to(jnp.asarray(
            self.eval(k, env, ax, binding, masks, ctx), jnp.int32), shape)
            for k in node.keys]
        dest_off = ctx.row_offsets.get(node.dest)
        dest_lim = ctx.array_limits.get(node.dest)
        ok = None if m is None else m
        if dest_lim is not None:          # logical bound, global coords
            lim_ok = kk[0] < dest_lim
            ok = lim_ok if ok is None else ok & lim_ok
        if dest_off is not None:          # localize to the shard block
            kk[0] = kk[0] - dest_off
        if ok is not None:                # condition/pad drops: sentinel
            kk[0] = jnp.where(ok, kk[0], dest.shape[0])
        # uint32 reinterpretation: negative/OOB keys wrap past the dims
        # and drop natively — no per-dim bounds selects, no signed-index
        # normalization (see _exec_segment)
        kk = [k.astype(jnp.uint32) for k in kk]
        return dest.at[tuple(kk)].set(val.astype(dest.dtype), mode="drop")

    # ---- reductions ----
    def _segment_backend(self, node: P.SegmentReduce, n_rows, dest):
        """Resolve the group-by backend for this node at trace time: a
        pinned backend is honored verbatim; "auto" asks the selector with
        the concrete shape class (rows reduced, flattened segment count,
        dtype, and the destination's analyzed sharding)."""
        if node.backend != "auto":
            self.note(node, f"segment:{node.backend}[pinned]")
            return node.backend
        kflat = 1
        for d_ in dest.shape:
            kflat *= int(d_)
        sh = (node.shardings or {}).get(node.dest)
        dec = self.selector.choose_segment(
            n=int(n_rows), k=kflat, d=1, op=node.op, dtype=str(dest.dtype),
            dest_dist=sh.dist.name if sh is not None else "REP",
            candidates=node.candidates)
        self.note(node, f"segment:{dec.backend}[{dec.source}]")
        return dec.backend

    def _exec_segment(self, node: P.SegmentReduce, env, ctx):
        # named scopes split the group-by's ops by stage: keys (the key
        # grid, row masks, ravel and salting), value, then in
        # _segment_flat sort / reduce, then salt_fold, combine
        dest = env[node.dest]
        with jax.named_scope("keys"):
            ax, binding, conds, base = self.build_space(node.space, env, ctx)
            masks = list(base)
            keys = [self.eval(k, env, ax, binding, masks, ctx)
                    for k in node.keys]
        shape = ax.shape()
        premapped: list = []
        with jax.named_scope("value"):
            val = jnp.broadcast_to(
                self.eval(node.value, env, ax, binding, masks, ctx,
                          premapped), shape)
        with jax.named_scope("keys"):
            m = self._mask(conds, env, ax, binding, masks, ctx)
            kk = [jnp.broadcast_to(jnp.asarray(k, jnp.int32), shape)
                  for k in keys]
        lim0 = ctx.array_limits.get(node.dest)
        n_rows = 1
        for d_ in shape:
            n_rows *= d_
        backend = self._segment_backend(node, n_rows, dest)
        salt_s, salt_src = self._segment_salt(node, ctx, dest)
        tag = self.decisions.get(id(node), "")
        if salt_s > 1:
            tag += f" salt={salt_s}x[{salt_src}]"
        if premapped:
            tag += " premap=" + ",".join(premapped)
        self.note(node, tag)
        if salt_s > 1:
            # hot-key salting: spread every key over S sub-destinations —
            # `salt*K + key` with salt = global row index mod S — reduce a
            # [S·K] partial, then ⊕-fold the [S, K] view back to [K] (salt
            # major: a [K, S] view puts S minor, which a TPU pads to 128
            # lanes, 8.7 GB for K = 17M and S = 16).  The
            # fold over ALL S slots makes any salt assignment correct (⊕ is
            # associative-commutative); the global row index keeps the
            # assignment identical on one device and across shards.  Every
            # backend takes the flattened-partial route here, including
            # scatter — salting exists to break its duplicate-update
            # serialization, and scattering into the [K·S] identity-filled
            # partial is exactly how the chain length divides by S.
            with jax.named_scope("keys"):
                flat, num = self._ravel_keys([k.reshape(-1) for k in kk],
                                             dest.shape, limit0=lim0)
                if m is not None:
                    flat = jnp.where(m.reshape(-1), flat, num)
                off = 0
                lead = node.space.axes[0] if node.space.axes else None
                if lead is not None and lead.kind == "bag":
                    off = ctx.bag_offsets.get(lead.bag, 0)
                salt = (off + jnp.arange(flat.shape[0], dtype=jnp.int32)) \
                    % salt_s
                salted = jnp.where(flat < num, salt * num + flat,
                                   num * salt_s)
            with jax.named_scope("value"):
                vflat = val.reshape(-1).astype(dest.dtype)
            part = self._segment_flat(backend, salted, vflat,
                                      num * salt_s, node.op)
            with jax.named_scope("salt_fold"):
                part = REDUCE[node.op](part.reshape(salt_s, num), axis=0)
            with jax.named_scope("combine"):
                return COMBINE[node.op](
                    dest, part.reshape(dest.shape).astype(dest.dtype))
        if backend != "scatter":
            # flattened-segment backends (sort / onehot / pallas): ravel
            # the key tuple against the physical dims, route every dropped
            # row (OOB key, negative key, padded row, failed condition) to
            # the sentinel segment `num`, reduce into a [num] partial and
            # ⊕-combine with the destination.  Empty segments carry the ⊕
            # identity in the partial, so the combine leaves them alone.
            with jax.named_scope("keys"):
                flat, num = self._ravel_keys([k.reshape(-1) for k in kk],
                                             dest.shape, limit0=lim0)
                if m is not None:
                    flat = jnp.where(m.reshape(-1), flat, num)  # dropped
            with jax.named_scope("value"):
                vflat = val.reshape(-1).astype(dest.dtype)
            seg = self._segment_flat(backend, flat, vflat, num, node.op)
            with jax.named_scope("combine"):
                return COMBINE[node.op](
                    dest, seg.reshape(dest.shape).astype(dest.dtype))
        # native scatter-⊕ straight into the destination with drop
        # semantics — no identity-filled segment array, no index
        # flattening.  Keys are reinterpreted as uint32: a negative key
        # wraps to ≥ 2^31, far beyond any dimension, so the scatter's own
        # mode="drop" bounds check drops it natively — the paper's §3.4
        # OOB-write-drops semantics with NO sentinel select, and XLA
        # skips the signed-index normalization chain entirely (2 selects
        # + 2 compares per scatter on the hot group-by path).  Rows
        # dropped for other reasons — a failed condition, an out-of-range
        # value gather, a padded row — scatter the ⊕ IDENTITY instead:
        # contributing the identity is contributing nothing (and it also
        # scrubs the non-finite values a dropped row may carry).
        with jax.named_scope("value"):
            val = val.astype(dest.dtype)
            if m is not None:
                val = jnp.where(m, val, identity(node.op, dest.dtype))
        with jax.named_scope("keys"):
            if lim0 is not None:      # logical dim-0 bound (padded rows)
                kk[0] = jnp.where(kk[0] >= lim0, dest.shape[0], kk[0])
            kk = [k.astype(jnp.uint32) for k in kk]
        with jax.named_scope("reduce"):
            return _scatter_op(dest.at[tuple(kk)], node.op)(val,
                                                            mode="drop")

    def _segment_salt(self, node: P.SegmentReduce, ctx, dest):
        """Resolve the hot-key salt factor for this node: the static hint
        (`node.salt` — user-set or planner-stamped from
        `PlanConfig.skew_salting`) wins; otherwise the caller's run-time
        probe result (`ctx.salts`).  Restricted to single-key 1-D
        destinations — multi-key ravels already interleave destinations,
        and the fold is defined on the flat [K·S] partial."""
        if len(node.keys) != 1 or len(dest.shape) != 1:
            return 1, None
        if node.salt is not None:
            return (int(node.salt), "hint") if node.salt > 1 else (1, None)
        s = ctx.salts.get(node.dest)
        if s is not None and int(s) > 1:
            return int(s), "probe"
        return 1, None

    def _segment_flat(self, backend: str, ids, vals, num: int, op: str):
        """[N]-flat segment-⊕ partial via the chosen backend.  `ids` ==
        `num` marks dropped rows; the partial's row i is the ⊕ of all
        vals whose id == i, with the ⊕ identity for empty segments."""
        if backend == "scatter":
            # scatter-⊕ into an identity-filled [num+1] partial (salted
            # path only: unsalted scatter goes straight into the dest).
            # Sentinel rows land in the discard row and are sliced off —
            # dropped rows may carry non-finite values, but they only ever
            # touch buf[num].
            with jax.named_scope("reduce"):
                buf = jnp.full((num + 1,), identity(op, vals.dtype),
                               vals.dtype)
                return _scatter_op(buf.at[ids], op)(vals)[:num]
        if backend == "sort":
            # sort-based: jax.ops.segment_⊕ over sorted ids (the classic
            # GPU/TPU shape).  One key/value sort carries the values with
            # their ids, so no gather applies a permutation after it;
            # stable, so each segment's rows reach the reduction in input
            # order and float sums do not depend on the sort.  num+1
            # segments so the sentinel rows land in a discard row —
            # deterministic drop without scatter modes.
            with jax.named_scope("sort"):
                ids, vals = jax.lax.sort_key_val(ids, vals, is_stable=True)
            seg = {"+": jax.ops.segment_sum, "min": jax.ops.segment_min,
                   "max": jax.ops.segment_max,
                   "*": jax.ops.segment_prod}[op]
            with jax.named_scope("reduce"):
                return seg(vals, ids, num_segments=num + 1,
                           indices_are_sorted=True)[:num]
        if backend == "onehot":
            # group-by as matmul: [N, num] one-hot × [N] values on the
            # MXU.  Integer values take the exact-int path (int32
            # accumulation); floats accumulate in f32.  Sentinel rows'
            # VALUES must be zeroed too: their one-hot row is all zeros,
            # but 0 × inf/NaN would still contaminate the dot — dropped
            # rows may carry non-finite values (e.g. a condition guarding
            # a division), and drop semantics say they contribute nothing
            acc = vals.dtype if jnp.issubdtype(vals.dtype, jnp.integer) \
                else jnp.float32
            with jax.named_scope("reduce"):
                vals = jnp.where(ids == num, jnp.zeros((), vals.dtype), vals)
                oh = (ids[:, None] == jnp.arange(num)[None, :]).astype(acc)
                return jax.lax.dot_general(
                    vals.astype(acc)[None, :], oh, (((1,), (0,)), ((), ())),
                    precision=xla_f32_dot(),
                    preferred_element_type=acc)[0]
        if backend == "pallas":
            from ..kernels import ops as kops
            with jax.named_scope("reduce"):
                return kops.segment_reduce(ids, vals, num, op=op)
        raise RejectionError(f"unknown segment backend {backend!r}")

    def _ravel_keys(self, kk, dshape, limit0=None):
        """Flatten index tuples against the PHYSICAL dims (strides must
        match the later reshape); `limit0` bounds dim-0 keys by the logical
        row count when the destination rows were padded."""
        num = 1
        for d in dshape:
            num *= d
        flat = jnp.zeros_like(kk[0])
        ok = jnp.ones_like(kk[0], dtype=bool)
        for dim_i, (k, d) in enumerate(zip(kk, dshape)):
            hi = limit0 if dim_i == 0 and limit0 is not None else d
            ok &= (k >= 0) & (k < hi)
            flat = flat * d + jnp.clip(k, 0, d - 1)
        flat = jnp.where(ok, flat, num)
        return flat, num

    def _keyed_combine(self, dest, partial, key_axes, ax, binding, op,
                       in_key_order, dest_off=None, dest_lim=None,
                       dest_name=None, ctx: ExecContext = _EMPTY_CTX):
        """Scatter-⊕ a partial (indexed by the key axes) into dest.
        `dest_off` localizes dim-0 rows to the shard's block; `dest_lim`
        drops rows at or beyond the logical row count (padding)."""
        if not in_key_order:
            cur = [a for a in ax.order if a in key_axes]
            partial = jnp.transpose(partial,
                                    [cur.index(a) for a in key_axes])
        los = [binding[a][2] for a in key_axes]
        exts = [ax.extent[a] for a in key_axes]
        # alignment certificate: the destination's local block IS the round
        # axis' window, so the traced window start is local row 0.  Rows
        # beyond the logical limit carry the ⊕ identity in the partial
        # (masked upstream), so the full-block combine leaves pad rows
        # untouched — no dynamic scatter inside the shard.
        if dest_name is not None and dest_name in ctx.aligned and key_axes \
                and key_axes[0] in ctx.axis_overrides \
                and not isinstance(los[0], int) \
                and exts[0] == dest.shape[0]:
            los[0] = 0
            dest_off = None
            dest_lim = None
        static0 = all(isinstance(l, int) and l == 0 for l in los)
        if tuple(exts) == dest.shape and static0 and dest_lim is None:
            return COMBINE[op](dest, partial.astype(dest.dtype))
        rows = los[0] + jnp.arange(exts[0])
        if dest_lim is not None:
            ok = rows < dest_lim
            local = rows if dest_off is None else rows - dest_off
            rows = jnp.where(ok, local, dest.shape[0])
        elif dest_off is not None:
            rows = rows - dest_off
        grids = tuple(
            (rows if i == 0 else los[i] + jnp.arange(exts[i])).reshape(
                [-1 if j == i else 1 for j in range(len(exts))])
            for i in range(len(exts)))
        return _scatter_op(dest.at[grids], op)(
            partial.astype(dest.dtype), mode="drop")

    def _exec_axis_reduce(self, node: P.AxisReduce, env, ctx):
        ax, binding, conds, base = self.build_space(node.space, env, ctx)
        dest = env[node.dest]
        contracted = node.contracted
        # dense fast path (pass: dense-fastpath): the value is a certified
        # +-product of gathers — contract on the MXU via jnp.einsum instead
        # of materializing the dense iteration grid.  The plan-level
        # operator stays AxisReduce; only the materialization changes.
        if node.product is not None and not conds \
                and self._mxu_masks_ok(node.space, node.key_axes, ctx):
            partial = self._product_partial(node.product, node.key_axes, ax,
                                            binding, env, ctx)
            if partial is not None:
                partial = self._limit_mask_partial(partial, node.key_axes,
                                                   ctx)
                self.note(node, "mxu-einsum")
                return self._keyed_combine(
                    dest, partial, node.key_axes, ax, binding, "+",
                    in_key_order=True,
                    dest_off=ctx.row_offsets.get(node.dest),
                    dest_lim=ctx.array_limits.get(node.dest),
                    dest_name=node.dest, ctx=ctx)
        self.note(node, "dense-grid")
        masks = list(base)
        val = self.eval(node.value, env, ax, binding, masks, ctx)
        m = self._mask(conds, env, ax, binding, masks, ctx)
        val = jnp.broadcast_to(val, ax.shape())
        if m is not None:
            val = jnp.where(m, val, identity(node.op, val.dtype))
        if contracted:
            partial = REDUCE[node.op](
                val, axis=tuple(ax.pos(a) for a in contracted))
        else:
            partial = val
        return self._keyed_combine(dest, partial, node.key_axes, ax, binding,
                                   node.op, in_key_order=False,
                                   dest_off=ctx.row_offsets.get(node.dest),
                                   dest_lim=ctx.array_limits.get(node.dest),
                                   dest_name=node.dest, ctx=ctx)

    # ---- contractions (runtime guards; fall back on failure) ----
    def _mxu_masks_ok(self, space: P.IterSpace, key_axes, ctx) -> bool:
        """Masks admissible on an MXU contraction: only the LEADING KEY
        axis may carry a pad limit (its out-of-limit partial rows are
        zeroed by `_limit_mask_partial` before combining).  A limit on a
        contracted axis or a padded bag axis would let pad rows contribute
        to kept outputs, so those take the masked dense-grid path."""
        for a in space.axes:
            if a.kind == "bag":
                if ctx.bag_limits.get(a.bag) is not None:
                    return False
            else:
                ov = ctx.axis_overrides.get(a.var)
                if ov is not None and ov[2] is not None and \
                        (not key_axes or a.var != key_axes[0]):
                    return False
        return True

    def _limit_mask_partial(self, partial, key_axes, ctx):
        """Zero the partial's out-of-limit leading rows (round-axis
        padding).  Zero is the + identity, so the combine can never
        perturb the destination's pad rows — preserving the system
        invariant that pad rows stay zero."""
        ov = ctx.axis_overrides.get(key_axes[0]) if key_axes else None
        if ov is None or ov[2] is None:
            return partial
        off, ext, lim, _tot = ov
        keep = (off + jnp.arange(ext)) < lim
        keep = keep.reshape((-1,) + (1,) * (jnp.ndim(partial) - 1))
        return jnp.where(keep, partial, jnp.zeros((), partial.dtype))

    def _sliced_operand(self, arr, name, faxes, ax, binding, ctx,
                        pad_ok=True):
        """Slice a contraction operand to the iteration extents along each
        factor axis; None when an offset/extent guard fails.

        Traced offsets (per-shard rounds) are admitted only under a static
        certificate — lax.dynamic_slice clamps out-of-range starts
        silently, so no slice is emitted that cannot be PROVEN in bounds:

        * `name in ctx.aligned` (dim 0): the operand's local block IS the
          round axis' window; no slice at all, local rows 0..extent.
        * global operand (never localized): the axis' padded global extent
          `total` is static; when total ≤ the physical dim, every window
          [offset, offset+extent) ⊆ [0, total) ⊆ [0, dim) — dynamic_slice
          cannot clamp (the bounds certificate, DESIGN.md §7).
        """
        for dim_i, (d, axn) in enumerate(zip(arr.shape, faxes)):
            lo = binding[axn][2]
            ext = ax.extent[axn]
            if isinstance(lo, int):
                if lo != 0 or ext != d:
                    if lo + ext > d:
                        return None
                    arr = jax.lax.slice_in_dim(arr, lo, lo + ext,
                                               axis=dim_i)
                continue
            if dim_i == 0 and name in ctx.aligned:
                if ext != d:
                    return None      # certificate requires block == window
                continue
            ov = ctx.axis_overrides.get(axn)
            if ov is not None and name not in ctx.row_offsets \
                    and ov[3] is not None and (ov[3] <= d or pad_ok):
                if ov[3] > d:
                    # physical dim shorter than the padded axis (an unpadded
                    # replicated operand on a non-divisible axis): zero-pad
                    # it to `total` first, making the window provably in
                    # bounds.  Only +-contraction callers may opt in
                    # (pad_ok): a zero row reproduces the empty-bag
                    # semantics of an out-of-range read under +, and rows
                    # at or beyond the logical limit are masked out of
                    # every kept output anyway.
                    pad = [(0, 0)] * arr.ndim
                    pad[dim_i] = (0, ov[3] - d)
                    arr = jnp.pad(arr, pad)
                arr = jax.lax.dynamic_slice_in_dim(arr, lo, ext, axis=dim_i)
                continue
            return None
        return arr

    def _product_partial(self, ef: P.EinsumFactors, key_axes, ax, binding,
                         env, ctx: ExecContext = _EMPTY_CTX):
        """jnp.einsum over the factor gathers; None when an offset/extent
        guard fails (caller falls back).  Padded operands are safe here:
        every slice is statically proven in bounds, pad rows are zero by
        system invariant, and the contraction monoid is +, whose identity
        matches the zero pad rows.  Factors covering only a subset of the
        key axes (contraction-free terms) come back expanded with size-1
        dims, ready to broadcast against full-key partials."""
        from .tiles import TiledMatrix, unpack
        letters = {a: chr(ord('a') + i) for i, a in enumerate(ax.order)}
        specs = []
        operands = []
        used: set = set()
        for f, faxes in zip(ef.factors, ef.factor_axes):
            arr = env[f.array]
            if isinstance(arr, TiledMatrix):
                arr = unpack(arr)
            spec = "".join(letters[axn]
                           for _, axn in zip(arr.shape, faxes))
            arr = self._sliced_operand(arr, f.array, faxes, ax, binding,
                                       ctx)
            if arr is None:
                return None
            specs.append(spec)
            operands.append(arr)
            used.update(faxes)
        out_axes = [a for a in key_axes if a in used]
        out_spec = "".join(letters[a] for a in out_axes)
        res = jnp.einsum(",".join(specs) + "->" + out_spec, *operands,
                         precision=xla_f32_dot())
        if tuple(out_axes) != tuple(key_axes):
            res = jnp.reshape(
                res, [ax.extent[a] if a in used else 1 for a in key_axes])
        for o in ef.others:
            res = res * self.eval(o, env, ax, binding, [], ctx)
        return res

    def _terms_partial(self, node: P.EinsumContract, ax, binding, env,
                       ctx: ExecContext = _EMPTY_CTX):
        key_axes = node.key_axes
        contracted = node.contracted
        key_exts = tuple(ax.extent[a] for a in ax.order if a in key_axes)
        cur = [a for a in ax.order if a in key_axes]
        perm = [cur.index(a) for a in key_axes]
        mult = 1
        for a in contracted:
            mult *= ax.extent[a]
        total = None
        for sign, term, ef, free in node.terms:
            if ef is not None:
                part = self._product_partial(ef, key_axes, ax, binding, env,
                                             ctx)
                if part is None:
                    return None
                if free:        # Σ over the contracted axes of a term free
                    part = part * mult      # of them = extent-product × term
            else:               # unrecognized contraction-free term:
                masks: list = []            # grid-evaluate (Σ_j c = |j|·c)
                v = self.eval(term, env, ax, binding, masks, ctx)
                if masks:
                    return None
                if jnp.ndim(v) == 0:
                    part = jnp.broadcast_to(v, key_exts)
                else:  # full-rank with size-1 contracted dims: drop them
                    part = jnp.squeeze(
                        v, axis=tuple(ax.pos(a) for a in contracted))
                    part = jnp.broadcast_to(part, key_exts)
                part = jnp.transpose(part, perm) * mult
            total = part * sign if total is None else total + part * sign
        for sc in node.scalars:
            total = total * self.eval(sc, env, ax, binding, [], ctx)
        return jnp.broadcast_to(total,
                                tuple(ax.extent[a] for a in key_axes))

    def _exec_einsum(self, node: P.EinsumContract, env, ctx):
        ax, binding, conds, base = self.build_space(node.space, env, ctx)
        partial = None
        # the candidate set IS the guard chain; op_select="force:dense-grid"
        # narrows it to the fallback, skipping the einsum attempt entirely
        if "einsum" in node.candidates and \
                self._mxu_masks_ok(node.space, node.key_axes, ctx):
            if node.product is not None:
                partial = self._product_partial(node.product, node.key_axes,
                                                ax, binding, env, ctx)
            else:
                partial = self._terms_partial(node, ax, binding, env, ctx)
        if partial is None:
            self.note(node, "fallback:dense-grid")
            return self.run_node(node.fallback, env, ctx)
        partial = self._limit_mask_partial(partial, node.key_axes, ctx)
        self.note(node, "einsum")
        dest = env[node.dest]
        return self._keyed_combine(dest, partial, node.key_axes, ax, binding,
                                   "+", in_key_order=True,
                                   dest_off=ctx.row_offsets.get(node.dest),
                                   dest_lim=ctx.array_limits.get(node.dest),
                                   dest_name=node.dest, ctx=ctx)

    def _exec_tiled(self, node: P.TiledMatmul, env, ctx):
        from .tiles import TiledMatrix, matmul_tiled, unpack
        ein = node.contract
        lhs = env[node.lhs]
        if not isinstance(lhs, TiledMatrix):
            return self.run_node(ein, env, ctx)
        ax, binding, conds, base = self.build_space(node.space, env, ctx)
        if base:
            return self.run_node(ein, env, ctx)
        # packed lhs must be used at full extent (no slicing on tiles)
        for d, axn in zip(lhs.shape, ein.product.factor_axes[0]):
            lo = binding[axn][2]
            if not isinstance(lo, int) or lo != 0 or ax.extent[axn] != d:
                return self.run_node(ein, env, ctx)
        rhs = env[node.rhs]
        if isinstance(rhs, TiledMatrix):
            rhs = unpack(rhs)
        rhs = self._sliced_operand(rhs, node.rhs, ein.product.factor_axes[1],
                                   ax, binding, ctx)
        if rhs is None:
            return self.run_node(ein, env, ctx)
        # packed lhs, guards passed: op_select decides whether the
        # block-sparse Pallas kernel or unpack+einsum contracts — the
        # former wins on the target MXU, the latter everywhere Pallas
        # would run in (python-level) interpret mode.  Both consume the
        # packed representation; only the materialization differs.  A
        # single-element candidate set (op_select="force:<b>") is honored
        # verbatim.
        if len(node.candidates) == 1:
            choice, src = node.candidates[0], "pinned"
        else:
            dec = self.selector.choose_contract(
                m=int(lhs.shape[0]), k=int(lhs.shape[1]),
                n=int(rhs.shape[1]), candidates=node.candidates)
            choice, src = dec.backend, dec.source
        if choice == "unpack-einsum":
            self.note(node, f"tiled:unpack-einsum[{src}]")
            return self.run_node(ein, env, ctx)
        self.note(node, f"tiled:pallas-tiled[{src}]")
        res = matmul_tiled(lhs, rhs)
        for o in ein.product.others:
            res = res * self.eval(o, env, ax, binding, [], ctx)
        dest = env[node.dest]
        return self._keyed_combine(dest, res, ein.key_axes, ax, binding,
                                   "+", in_key_order=True,
                                   dest_off=ctx.row_offsets.get(node.dest),
                                   dest_lim=ctx.array_limits.get(node.dest),
                                   dest_name=node.dest, ctx=ctx)

    # ---- scalar reductions ----
    def _total_reduce(self, node: P.ScalarReduce, env, ax, binding, conds,
                      base, ctx: ExecContext = _EMPTY_CTX):
        masks: list = []
        if node.bool_any is not None and not base:
            # peephole: max/min over float(bool) → any/all (XLA-CPU f32
            # max-reduce is ~20x slower than a bool reduce; same result)
            b = self.eval(node.bool_any, env, ax, binding, masks, ctx)
            if not masks and ax.order:
                red = jnp.any if node.op == "max" else jnp.all
                return red(jnp.asarray(b, bool)).astype(jnp.float32)
        masks = list(base)
        val = self.eval(node.value, env, ax, binding, masks, ctx)
        m = self._mask(conds, env, ax, binding, masks, ctx)
        val = jnp.broadcast_to(val, ax.shape()) if ax.order else val
        if m is not None:
            val = jnp.where(m, val, identity(node.op,
                                             jnp.asarray(val).dtype))
        return REDUCE[node.op](val) if ax.order else val

    def _exec_scalar_reduce(self, node: P.ScalarReduce, env, ctx):
        ax, binding, conds, base = self.build_space(node.space, env, ctx)
        total = self._total_reduce(node, env, ax, binding, conds, base, ctx)
        dest = env[node.dest]
        if node.point is not None:      # Rule 16: one-cell ⊕ update
            return _scatter_op(dest.at[node.point], node.op)(
                total.astype(dest.dtype))
        dest = jnp.asarray(dest)
        return COMBINE[node.op](dest, total.astype(dest.dtype))

    # ---- sequential loop ----
    def _exec_seq_loop(self, node: P.SeqLoop, env, ctx):
        carry0 = tuple(jnp.asarray(env[n]) for n in node.carry)

        def cond_fn(c, _names=node.carry, _n=node):
            e2 = dict(env)
            e2.update(dict(zip(_names, c)))
            return jnp.asarray(
                self.eval(_n.cond, e2, Axes(), {}, [], ctx), bool)

        def body_fn(c, _names=node.carry, _n=node):
            e2 = dict(env)
            e2.update(dict(zip(_names, c)))
            self.execute(_n.body, e2, ctx)
            return tuple(jnp.asarray(e2[n]) for n in _names)

        out = jax.lax.while_loop(cond_fn, body_fn, carry0)
        env.update(dict(zip(node.carry, out)))

    def eval_scalar(self, e, env):
        """Evaluate an expression outside any iteration space."""
        return self.eval(e, env, Axes(), {}, [])


# ---------------------------------------------------------------------------
# program compilation
# ---------------------------------------------------------------------------

class CompiledProgram:
    def __init__(self, prog: Program, target, optimize_contractions=True,
                 use_kernels=False, infer_distributions=True,
                 dense_fastpath=True, op_select="cost",
                 autotune_cache=None, compile_mode="whole",
                 donate=False, round_fusion=True,
                 skew_rebalance=True, skew_salting="auto",
                 out_of_core="auto", memory_budget=None, chunk_rows=None,
                 lineage=True, speculative=True):
        self.program = prog
        self.target = target
        from .op_select import CACHE_FILE, OpSelector
        if autotune_cache is None:
            autotune_cache = CACHE_FILE
        self.config = PlanConfig(optimize_contractions=optimize_contractions,
                                 use_kernels=use_kernels,
                                 infer_distributions=infer_distributions,
                                 dense_fastpath=dense_fastpath,
                                 op_select=op_select,
                                 autotune_cache=autotune_cache,
                                 round_fusion=round_fusion,
                                 skew_rebalance=skew_rebalance,
                                 skew_salting=skew_salting,
                                 out_of_core=out_of_core,
                                 memory_budget=memory_budget,
                                 chunk_rows=chunk_rows,
                                 lineage=lineage,
                                 speculative=speculative)
        self.plan = plan_program(target, prog, self.config)
        from .dist_analysis import collect
        self.dists = collect(self.plan)   # array → Dist (pass-8 annotations)
        self.selector = OpSelector(op_select, cache_path=autotune_cache)
        self.executor = PlanExecutor(prog, self.selector)
        # ---- whole-program compilation (DESIGN.md §9) ----
        # run() traces the ENTIRE plan into one cached jax.jit computation
        # per (static dims, shapes, dtypes) signature — one XLA dispatch
        # per call instead of one per node.  compile_mode="eager" keeps the
        # per-node path (the guaranteed fallback, also taken automatically
        # when a trace fails or an input arrives §5-packed).  `donate`
        # additionally donates the buffers of mutated destinations and
        # SeqLoop carries to the computation — callers passing jax arrays
        # must not reuse them after the call (numpy inputs are copied to
        # device per call, so donation is always safe for them).
        self.compile_mode = compile_mode
        self.donate = donate
        self._whole_cache: dict = {}   # signature → (fn, decisions snapshot)
        # per-SIGNATURE compile-failure memoization (DESIGN.md §11): a
        # failed whole-program trace disables only ITS signature, for
        # policy.disable_ttl runs — other shapes keep the whole path, and
        # the expired signature gets re-attempted (bounded retry budget)
        self._whole_bad: dict = {}     # signature key → remaining ttl
        self.trace_count = 0           # whole-program traces (test probe)
        self.cache_hits = 0
        self.trace_failures = 0        # failed whole traces (probe)
        self.whole_retries = 0         # expired disables re-attempted
        self.faults = F.FaultLedger(prog.name)   # failure ledger (§11);
        self.policy = F.RetryPolicy()  # shared with DistributedProgram
        self._last_whole_exc = None    # why the LAST _run_whole descended
        # ---- out-of-core capacity tier (DESIGN.md §12) ----
        # out_of_core: "auto" = admit against memory_budget when set, and
        # descend to chunked streaming on classified capacity errors;
        # "force" = every run() streams; "off" = pre-§12 ladder (capacity
        # bottoms out at interp/single-device).  chunk_rows pins the tile;
        # None derives it from the budget via memest/choose_chunk_rows.
        self.out_of_core = out_of_core
        self.memory_budget = memory_budget
        self.chunk_rows = chunk_rows
        self._chunker = None           # lazy chunked.ChunkRunner
        self._mem_last = None          # last memest.MemEstimate (explain)
        self._mem_cache: dict = {}     # shape key → MemEstimate
        self._donate_names = frozenset(
            d for n in self.plan for d in P.dests_of(n)
            if prog.params.get(d) is not None
            and prog.params[d].kind != "dim")

    @property
    def _whole_disabled(self) -> bool:
        """Back-compat probe: True while ANY signature is sitting out its
        disable ttl (the old flag was global AND permanent — §11 made it
        per-signature with a bounded retry budget)."""
        return bool(self._whole_bad)

    def pretty_target(self) -> str:
        return "\n".join(pretty(s) for s in self.target)

    def explain(self, tiled=()) -> str:
        """Spark-EXPLAIN-style dump of the chosen physical operator per
        statement.  `tiled` names params assumed to arrive §5-packed.
        After a run(), nodes whose backend the operator-selection
        subsystem resolved at trace time carry a `selected:` line (e.g.
        ``selected: segment:scatter[cost]``).  The trailing
        `whole-program:` line reports the compile-cache state — how many
        signatures were traced and how many run() calls hit the cache."""
        text = P.explain(self.plan, self.program.name, tiled,
                         decisions=self.executor.decisions)
        mode = "eager" if self.compile_mode != "whole" or \
            self._whole_disabled else "whole"
        text += (f"\nwhole-program: mode={mode}, {self.trace_count} traced, "
                 f"{self.cache_hits} cache hits"
                 + (", donate=on" if self.donate else "")
                 + (f", {self.trace_failures} trace failures "
                    f"({len(self._whole_bad)} signatures sitting out ttl, "
                    f"{self.whole_retries} re-attempted)"
                    if self.trace_failures or self.whole_retries else ""))
        if self._mem_last is not None:
            text += "\n" + self._mem_last.summary(self.memory_budget)
        return text

    # ---- out-of-core capacity tier (DESIGN.md §12) ----
    @property
    def chunker(self):
        if self._chunker is None:
            from .chunked import ChunkRunner
            self._chunker = ChunkRunner(self)
        return self._chunker

    def estimate_memory(self, inputs: dict):
        """Peak-device-bytes estimate for this call's shapes
        (core/memest.py) — the admission-check input.  Cached per shape
        class; also surfaced through explain()/explain_memory()."""
        from . import memest
        senv = memest.shape_env(self.program, inputs)
        key = tuple(sorted((n, repr(e)) for n, e in senv.items()))
        est = self._mem_cache.get(key)
        if est is None:
            est = memest.estimate(self.plan, self.program, senv,
                                  donate=self.donate)
            self._mem_cache[key] = est
        self._mem_last = est
        return est

    def explain_memory(self, inputs: dict) -> str:
        return self.estimate_memory(inputs).explain(self.memory_budget)

    def explain_chunked(self) -> str:
        """The chunked (out-of-core) form of the plan, ChunkLoops shown."""
        return self.chunker.explain()

    def explain_lineage(self) -> str:
        """The per-round recovery recipes (core/lineage.py, DESIGN.md §13):
        one `lineage:` line per round naming the shard axis, the write
        taxonomy class, each read's surviving source (rep / aligned /
        gathered) and the producer-chain depth a restart would replay."""
        from .lineage import explain_lineage
        return explain_lineage(self.plan, self.program.name)

    def _ooc_admits(self, inputs: dict) -> bool:
        """True when this call must take the chunked tier up front: forced,
        or its estimated peak exceeds the memory budget (the hard
        admission check — chunk instead of letting XLA OOM)."""
        if self.out_of_core == "force":
            return True
        if self.out_of_core == "off" or self.memory_budget is None:
            return False
        est = self.estimate_memory(inputs)
        if est.peak_bytes > self.memory_budget:
            from .memest import fmt_bytes
            self.faults.record(
                "admission", "chunked",
                f"estimated peak {fmt_bytes(est.peak_bytes)} > budget "
                f"{fmt_bytes(self.memory_budget)}: streaming chunked")
            return True
        return False

    def _initial_chunk_rows(self, inputs: dict) -> int:
        if self.chunk_rows:
            return int(self.chunk_rows)
        from .chunked import DEFAULT_CHUNK_ROWS, choose_chunk_rows
        if self.memory_budget is not None:
            return choose_chunk_rows(self.estimate_memory(inputs),
                                     self.memory_budget)
        return DEFAULT_CHUNK_ROWS

    def _run_chunked(self, inputs: dict, *, observer=None, loop_state=None,
                     recovering=False):
        """The chunked rung: stream bag tiles through resident
        accumulators (core/chunked.py).  A capacity error INSIDE the
        stream halves the tile and retries — descending the memory curve,
        never ascending it — until a 1-row tile fails too."""
        rows = self._initial_chunk_rows(inputs)
        while True:
            try:
                out = self.chunker.run(inputs, chunk_rows=rows,
                                       observer=observer,
                                       loop_state=loop_state)
                if recovering:
                    self.faults.recover("chunked")
                return out
            except Exception as ex:           # noqa: BLE001 — ladder
                if F.classify(ex) != "capacity" or rows <= 1:
                    raise
                self.faults.descend(f"chunked[{rows}]",
                                    f"chunked[{rows // 2}]", ex)
                rows //= 2
                recovering = True

    # -- public execution interface (distributed.py consumes this) --
    def execute(self, env: dict, *, bag_offsets=None, bag_limits=None,
                array_limits=None, nodes=None, salts=None) -> None:
        ctx = ExecContext(bag_offsets or {}, bag_limits or {},
                          array_limits=array_limits or {},
                          salts=salts or {})
        self.executor.execute(self.plan if nodes is None else nodes, env, ctx)

    def prepare_env(self, inputs: dict) -> dict:
        env = {}
        for name, t in self.program.params.items():
            v = inputs[name]
            if t.kind == "dim":
                env[name] = int(v)
            elif t.kind == "bag":
                env[name] = tuple(jnp.asarray(c) for c in v) \
                    if isinstance(v, tuple) else (jnp.asarray(v),)
            elif t.kind in ("vector", "matrix", "map"):
                from .tiles import TiledMatrix
                if isinstance(v, TiledMatrix):
                    env[name] = v      # §5 packed input, fused where possible
                else:
                    env[name] = jnp.asarray(
                        v, jnp.float32 if t.dtype == "float" else jnp.int32)
            else:
                env[name] = jnp.asarray(v)
        return env

    # ---- whole-program path ----
    def _signature(self, env):
        """Compile-cache key: static dims by VALUE (they define shapes),
        arrays by shape+dtype.  None = this env cannot take the whole-
        program path (§5 packed inputs execute eagerly)."""
        from .tiles import TiledMatrix
        sig = []
        for name, t in self.program.params.items():
            v = env[name]
            if t.kind == "dim":
                sig.append((name, "dim", v))
            elif t.kind == "bag":
                sig.append((name, "bag", tuple(
                    (tuple(c.shape), str(c.dtype)) for c in v)))
            elif isinstance(v, TiledMatrix):
                return None
            else:
                sig.append((name, t.kind, tuple(jnp.shape(v)),
                            str(jnp.asarray(v).dtype)))
        return tuple(sig)

    def _run_whole(self, inputs: dict):
        with _span("repro.prepare"):
            env = self.prepare_env(inputs)
            sig = self._signature(env)
        if sig is None:
            return None                       # packed inputs: eager path
        static = {n: v for n, v in env.items() if isinstance(v, int)}
        # donation only applies at a real jit boundary: under an outer
        # trace (callers wrapping run() in their own jit) the donated
        # buffers are tracers and jax would warn and ignore them
        donate = self.donate and not any(
            isinstance(x, jax.core.Tracer)
            for v in env.values()
            for x in (v if isinstance(v, tuple) else (v,)))
        donated = {n: v for n, v in env.items()
                   if donate and n in self._donate_names
                   and not isinstance(v, int)}
        kept = {n: v for n, v in env.items()
                if n not in static and n not in donated}
        # run-time hot-key probe (skew salting): the resolved factors are
        # part of the cache key — a skewed and a uniform key stream of the
        # same shapes trace DIFFERENT programs.  The probe copies up to
        # 4096 key rows to the host, a device round trip per call
        with _span("repro.salt_probe"):
            salts = collect_salts(self.plan, env, self.selector,
                                  self.config.skew_salting)
        key = (sig, donate, tuple(sorted(salts.items())))
        left = self._whole_bad.get(key)
        if left is not None:
            # this signature's trace failed recently: sit out the rest of
            # its disable ttl at the eager level, then re-attempt (§11 —
            # the old behaviour disabled the whole PROGRAM forever)
            if left > 1:
                self._whole_bad[key] = left - 1
                return None
            del self._whole_bad[key]
            self.whole_retries += 1
            self.faults.record("retry", "whole",
                               "signature disable ttl expired: "
                               "re-attempting whole-program trace")
        ent = self._whole_cache.get(key)
        if ent is None:
            def traced(dnt, kpt, _static=dict(static)):
                e = dict(_static)
                e.update(dnt)
                e.update(kpt)
                self.executor.execute(self.plan, e,
                                      ExecContext(salts=salts))
                return {n: e[n] for n in self.program.outputs}

            fn = jax.jit(traced, donate_argnums=(0,) if donated else ())

            def attempt():
                F.site("lower.whole_trace", program=self.program.name)
                return fn(donated, kept)      # traces the whole plan once
            try:
                with _span("repro.compile"):    # trace, compile, 1st call
                    out = F.run_with_retries(attempt, policy=self.policy,
                                             ledger=self.faults,
                                             label="whole")
            except Exception as ex:           # noqa: BLE001 — ladder
                self.trace_failures += 1
                self._whole_bad[key] = self.policy.disable_ttl
                self._last_whole_exc = ex
                # capacity never ascends the memory curve (§12): the
                # chunked tier is the correct rung, not the eager path
                # (same buffers, same OOM) — run() routes on the class
                to = "chunked" if (F.classify(ex) == "capacity"
                                   and self.out_of_core != "off") else "eager"
                self.faults.descend("whole", to, ex)
                return None                   # run() picks the rung
            self.trace_count += 1
            self._whole_cache[key] = (fn, dict(self.executor.decisions))
            return out
        fn, notes = ent
        self.cache_hits += 1
        # cached signatures skip the trace: restore the decision snapshot
        # taken when this signature was traced, so explain() stays accurate
        self.executor.decisions.update(notes)
        with _span("repro.enqueue"):
            return fn(donated, kept)

    def run(self, inputs: dict) -> dict:
        with _span("repro.run"):
            return self._run(inputs)

    def _run(self, inputs: dict) -> dict:
        # hard admission check (DESIGN.md §12): calls whose estimated
        # peak exceeds the memory budget stream chunked from the start
        if self._ooc_admits(inputs):
            return self._run_chunked(inputs)
        whole_failed = False
        if self.compile_mode == "whole":
            self._last_whole_exc = None
            out = self._run_whole(inputs)
            if out is not None:
                return out
            ex = self._last_whole_exc
            whole_failed = ex is not None
            if whole_failed and F.classify(ex) == "capacity" \
                    and self.out_of_core != "off":
                # whole → chunked: the capacity rung (never eager, which
                # re-materializes the same all-resident buffers)
                return self._run_chunked(inputs, recovering=True)

        def eager():
            env = self.prepare_env(inputs)
            self.execute(env, salts=collect_salts(
                self.plan, env, self.selector, self.config.skew_salting))
            return {n: env[n] for n in self.program.outputs}

        # degradation ladder (DESIGN.md §11/§12): whole → eager per-node
        # (the executor's own node fallback chains live inside) → chunked
        # streaming for capacity / interpreter oracle for the rest.
        # Transients retry at each level with bounded backoff;
        # deterministic errors get AT MOST one descent before surfacing.
        try:
            out = F.run_with_retries(eager, policy=self.policy,
                                     ledger=self.faults, label="eager")
            if whole_failed:
                self.faults.recover("eager")
            return out
        except Exception as ex:               # noqa: BLE001 — ladder
            if F.classify(ex) == "deterministic":
                # a user error reproduces at every level: it already got
                # its one descent (whole→eager) or none was available —
                # surface it, never fall through to the oracle (which
                # would silently mask it)
                raise
            if F.classify(ex) == "capacity" and self.out_of_core != "off":
                # eager → chunked: stream tiles instead of the oracle
                # (the oracle holds everything host-resident in float64 —
                # fine for correctness, wrong rung for capacity)
                self.faults.descend("eager", "chunked", ex)
                try:
                    return self._run_chunked(inputs, recovering=True)
                except Exception as ex2:      # noqa: BLE001 — ladder
                    if F.classify(ex2) == "deterministic":
                        raise
                    return self._run_interp(inputs, "chunked", ex2)
            # transient persisting past the eager retries: the reference
            # interpreter is the bottom rung — correct numpy float64
            # results (not bit-identical; the ledger says so)
            return self._run_interp(inputs, "eager", ex)

    def _run_interp(self, inputs: dict, from_level: str, ex) -> dict:
        self.faults.descend(from_level, "interp", ex)
        from .interp import run as _oracle
        out = _oracle(self.program, dict(inputs))
        self.faults.recover("interp")
        return {n: out[n] for n in self.program.outputs}

    def explain_faults(self) -> str:
        """Render the failure ledger (DESIGN.md §11) next to explain():
        retry/descent/recovery/straggler events plus the per-signature
        whole-program disable state."""
        text = self.faults.explain()
        text += (f"\nwhole-program: {self.trace_failures} trace failures, "
                 f"{len(self._whole_bad)} signatures sitting out ttl "
                 f"(budget {self.policy.disable_ttl} runs), "
                 f"{self.whole_retries} re-attempted")
        return text

    # ---- checkpointable execution (DESIGN.md §11) ----
    def run_stepwise(self, inputs: dict, *, loop_state=None, observer=None):
        """Eager execution with HOST-DRIVEN top-level sequential loops —
        the checkpoint/resume entry.  run() executes a SeqLoop as one
        on-device lax.while_loop, so no mid-loop state ever reaches the
        host; this path instead evaluates the condition and executes the
        body once per iteration, calling
        ``observer(loop_idx, iteration, carry_dict)`` after every
        iteration with the loop carry as live arrays — the hook
        runtime/ft.LoopRunner snapshots through CheckpointManager.

        ``loop_state`` maps loop_idx → (iteration, {carry: array}) and
        fast-forwards the matching loop: nodes before it re-execute
        (pure and deterministic from the same inputs), the carry is
        restored, and iteration continues from there.  A resumed run is
        bit-identical to an uninterrupted stepwise run because both
        execute the exact same per-iteration body computations on the
        same carry values.  Loop indices follow plan.seq_loops().

        Out-of-core runs route to the chunked plan, whose top-level
        ChunkLoops are SeqLoops in this numbering — the observer fires
        once per CHUNK with the accumulator carry, so LoopRunner
        checkpoints give chunk-granular resume of a killed streaming run
        with no extra machinery (DESIGN.md §12)."""
        if self._ooc_admits(inputs):
            return self._run_chunked(inputs, observer=observer,
                                     loop_state=loop_state)
        env = self.prepare_env(inputs)
        salts = collect_salts(self.plan, env, self.selector,
                              self.config.skew_salting)
        ctx = ExecContext(salts=salts)
        loop_state = dict(loop_state or {})
        li = 0
        for node in P.flatten(self.plan):
            if not isinstance(node, P.SeqLoop):
                self.executor.execute([node], env, ctx)
                continue
            it = 0
            st = loop_state.get(li)
            if st is not None:
                it, carry = st
                for c in node.carry:
                    env[c] = jnp.asarray(carry[c])
            while bool(self.executor.eval_scalar(node.cond, env)):
                F.site("lower.loop_iter", loop=li, iteration=it)
                self.executor.execute(node.body, env, ctx)
                it += 1
                if observer is not None:
                    observer(li, it, {c: env[c] for c in node.carry})
            li += 1
        return {n: env[n] for n in self.program.outputs}

    # ---- batchable entry (serving layer, DESIGN.md §10) ----
    # The PlanServer (serve/plans.py) coalesces concurrent invocations of
    # one program into a single vmapped whole-program XLA call.  These
    # three hooks are its contract: a HOST-SIDE mirror of prepare_env (so
    # requests canonicalize without touching the device), the signature
    # key that doubles as the shape-bucketing function, and the batched
    # call itself — the same traced plan, vmapped over a leading request
    # axis and cached in the SAME whole-program cache.

    def canonical_inputs(self, inputs: dict) -> dict:
        """Numpy mirror of prepare_env: same dtype coercions, no device
        transfer.  The serving layer stacks many of these host-side and
        ships ONE buffer per bucket.  §5 packed inputs are rejected —
        they execute eagerly and cannot batch."""
        from .tiles import TiledMatrix
        out = {}
        for name, t in self.program.params.items():
            v = inputs[name]
            if isinstance(v, TiledMatrix):
                raise ValueError(
                    f"param '{name}': packed (TiledMatrix) inputs cannot "
                    "take the batched serving path")
            if t.kind == "dim":
                out[name] = int(v)
            elif t.kind == "bag":
                cols = v if isinstance(v, tuple) else (v,)
                out[name] = tuple(
                    np.asarray(c, jax.dtypes.canonicalize_dtype(
                        np.asarray(c).dtype)) for c in cols)
            elif t.kind in ("vector", "matrix", "map"):
                out[name] = np.asarray(
                    v, np.float32 if t.dtype == "float" else np.int32)
            else:
                a = np.asarray(v)
                out[name] = np.asarray(
                    a, jax.dtypes.canonicalize_dtype(a.dtype))
        return out

    def entry_signature(self, cinputs: dict) -> tuple:
        """The whole-program compile-cache key of one canonicalized
        request: static dims BY VALUE, arrays by shape+dtype — exactly
        `_signature`, computed host-side.  This IS the serving layer's
        bucketing function: requests whose signatures agree after bag/row
        padding share one batched computation."""
        sig = []
        for name, t in self.program.params.items():
            v = cinputs[name]
            if t.kind == "dim":
                sig.append((name, "dim", int(v)))
            elif t.kind == "bag":
                sig.append((name, "bag", tuple(
                    (tuple(c.shape), str(c.dtype)) for c in v)))
            else:
                sig.append((name, t.kind, tuple(np.shape(v)),
                            str(np.asarray(v).dtype)))
        return tuple(sig)

    @property
    def bag_row_aligned(self) -> dict:
        """array → bag for dense params whose dim-0 rides a bag's row
        count (plan.bag_row_arrays): the arrays a shape bucket must pad in
        lockstep with that bag, under a matching `array_limits` mask."""
        if not hasattr(self, "_bag_row_aligned"):
            self._bag_row_aligned = P.bag_row_arrays(self.plan)
        return self._bag_row_aligned

    def batched_call(self, key, static: dict, arrays: dict, lengths: dict,
                     limit_bags=(), limit_arrays=(), salts=None):
        """Run the whole-program trace vmapped over a leading request
        axis: `arrays` maps every non-dim param to a [B, ...]-stacked
        value (bags as tuples of [B, N] columns), `lengths` maps each
        padded bag/bag-aligned array to its [B] logical row counts —
        threaded per lane through ExecContext.{bag,array}_limits so pad
        rows can never change a result (the same §3.4 machinery the
        distributed pad+mask path uses).  `key` is the caller's padded
        bucket signature (it must determine shapes, B, and the limit
        sets); entries live in the SAME `_whole_cache` as single-request
        signatures and count toward trace_count/cache_hits.  Mutated
        destinations are donated — callers pass freshly device_put
        buffers and must not reuse them.  `salts` (dest → hot-key salt
        factor) is decided by the caller from concrete host data, the
        same probe a solo run() takes, and must be part of `key` — keys
        are tracers under vmap.  Raises on trace failure — the serving
        layer falls back to sequential run() per request."""
        ck = ("batched", key)
        donated = {n: v for n, v in arrays.items()
                   if n in self._donate_names}
        kept = {n: v for n, v in arrays.items() if n not in donated}
        ent = self._whole_cache.get(ck)
        if ent is None:
            outs = tuple(self.program.outputs)
            lb, la = tuple(limit_bags), tuple(limit_arrays)
            salts = dict(salts or {})

            def traced(dnt, kpt, lens, _static=dict(static)):
                def one(d, k_, l):
                    e = dict(_static)
                    e.update(d)
                    e.update(k_)
                    ctx = ExecContext(
                        bag_limits={n: l[n] for n in lb},
                        array_limits={n: l[n] for n in la}, salts=salts)
                    self.executor.execute(self.plan, e, ctx)
                    return {n: e[n] for n in outs}
                return jax.vmap(one)(dnt, kpt, lens)

            fn = jax.jit(traced, donate_argnums=(0,) if donated else ())
            out = fn(donated, kept, lengths)   # traces the batch once
            self.trace_count += 1
            self._whole_cache[ck] = (fn, dict(self.executor.decisions))
            return out
        fn, notes = ent
        self.cache_hits += 1
        self.executor.decisions.update(notes)
        return fn(donated, kept, lengths)

    def __call__(self, **inputs):
        return self.run(inputs)


def compile_program(fn_or_prog, *, restrictions=True,
                    optimize_contractions=True,
                    use_kernels=False,
                    infer_distributions=True,
                    dense_fastpath=True,
                    op_select="cost",
                    autotune_cache=None,
                    compile_mode="whole",
                    donate=False,
                    round_fusion=True,
                    skew_rebalance=True,
                    skew_salting="auto",
                    out_of_core="auto",
                    memory_budget=None,
                    chunk_rows=None,
                    lineage=True,
                    speculative=True) -> CompiledProgram:
    """Front door: loop program → restrictions check (Def. 3.1) →
    comprehension translation (Fig. 2) → pass pipeline (passes.py) →
    executable physical plan.

    op_select picks the group-by-⊕ backend policy (DESIGN.md §8):
    "cost" (default) resolves each SegmentReduce's backend from the
    analytical shape-class cost model at trace time; "autotune" measures
    every candidate once per shape class and persists the winner to
    `autotune_cache` (default `.repro_autotune.json`, reloaded by later
    sessions and CI); "force:<backend>" pins one backend everywhere its
    candidate set allows (A/B tests).  use_kernels=True is the legacy
    flag form of "force:pallas" (the one-hot-MXU segment kernel;
    interpret-mode off-TPU).  infer_distributions=False pins every array
    to REP (replicated — the pre-analysis distributed behaviour);
    dense_fastpath=False disables the executor specialization pass
    (DenseMap / MXU AxisReduce / columnar certificates) — operators then
    always materialize the general way.

    compile_mode picks the execution strategy of run() (DESIGN.md §9):
    "whole" (default) traces the entire plan into ONE cached XLA
    computation per (dims, shapes, dtypes) signature; "eager" keeps the
    per-node dispatch path (also the automatic fallback when a whole-
    program trace fails or inputs arrive §5-packed).  donate=True
    additionally donates mutated destinations and SeqLoop carries at the
    jit boundary — callers must then treat jax-array inputs as consumed.
    round_fusion=False disables pass 11 (FusedRound regions / on-device
    distributed loops).

    skew_rebalance=False disables the explicit ONED_VAR→ONED_ROW rebalance
    insertion (skewed arrays then stay variable-block, the pad+mask
    fallback).  skew_salting picks the hot-key salting policy for
    group-bys: "auto" (default) resolves per node from the run-time skew
    probe + cost model, "off" pins S=1 everywhere, "force:<S>" salts every
    eligible group-by with factor S (A/B tests and goldens).

    Out-of-core (DESIGN.md §12): memory_budget (bytes) turns on the hard
    admission check — calls whose memest peak estimate exceeds it stream
    bag tiles through resident accumulators (core/chunked.py) instead of
    running all-resident; classified capacity errors (real XlaRuntimeError
    OOMs or injected ones) descend to the same chunked rung.
    out_of_core: "auto" (default) = admit + descend as above; "force" =
    every run streams (A/B tests); "off" = pre-§12 ladder.  chunk_rows
    pins the streaming tile; None derives it from the budget.

    Surgical recovery (DESIGN.md §13): lineage=True (default) annotates
    every round with its RoundLineage recovery recipe, so a shard lost
    mid-run is recomputed in place instead of descending the ladder;
    lineage=False restores the pre-§13 ladder-only behaviour.
    speculative=True (default) lets the straggler watchdog launch ≤1
    backup execution of a flagged round (first finisher wins);
    speculative=False keeps the watchdog log-only."""
    prog = fn_or_prog if isinstance(fn_or_prog, Program) \
        else fn_or_prog.program
    if restrictions:
        check_restrictions(prog)
    target = translate(prog)
    return CompiledProgram(prog, target, optimize_contractions, use_kernels,
                           infer_distributions, dense_fastpath, op_select,
                           autotune_cache, compile_mode, donate,
                           round_fusion, skew_rebalance, skew_salting,
                           out_of_core, memory_budget, chunk_rows,
                           lineage, speculative)
