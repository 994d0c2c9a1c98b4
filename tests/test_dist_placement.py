"""The distributed path on int32 bags: vertex ids past 2**24 stay exact,
placement moves no bag bytes it need not move, a fault-free run never
speculates, and the spans and scopes a trace reads are there.  Each runs in
a subprocess with 4 forced host devices (the main test process must keep
1 device)."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec
from repro.core import programs
from repro.core.distributed import RowBlocks, compile_distributed
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((4,), ("data",))
ROWS = NamedSharding(mesh, PartitionSpec("data"))


def pagerank_ref(src, dst, n, steps, b=0.85):
    C = np.bincount(src, minlength=n).astype(np.float64)
    P = np.full(n, 1.0 / n)
    with np.errstate(divide="ignore", invalid="ignore"):
        w = np.where(C > 0, 1.0 / C, 0.0)
        for _ in range(steps):
            P = (1.0 - b) / n + b * np.bincount(dst, weights=(P * w)[src],
                                                minlength=n)
    return P, C


def graph(n, m, seed=0):
    # a few hundred edges, a third of them at ids at and above 2**24
    rng = np.random.default_rng(seed)
    high = np.arange(2**24 - 4, n)
    src = np.concatenate([rng.integers(0, n, m), high, rng.choice(high, m)])
    dst = np.concatenate([rng.choice(high, m), rng.integers(0, n, len(high)),
                          rng.integers(0, n, m)])
    return src.astype(np.int32), dst.astype(np.int32)


def inputs(src, dst, n, steps=3):
    return dict(E=(src, dst), P=jnp.full(n, 1.0 / n, jnp.float32),
                NP=jnp.zeros(n, jnp.float32), C=jnp.zeros(n, jnp.float32),
                N=n, num_steps=float(steps), steps=0.0, b=0.85)


def check(out, src, dst, n, steps=3):
    P, C = pagerank_ref(src.astype(np.int64), dst.astype(np.int64), n, steps)
    got_p = np.asarray(out["P"], np.float64)
    got_c = np.asarray(out["C"], np.float64)
    assert got_c.shape == C.shape and np.array_equal(got_c, C), \\
        np.abs(got_c - C).max()
    rel = np.max(np.abs(got_p - P) / P)
    assert rel < 1e-5, rel
"""

_INT32_CODE = _PRELUDE + """
n = 2**24 + 8
src, dst = graph(n, 200)
dp = compile_distributed(programs.pagerank, mesh, ("data",),
                         out_of_core="off")
for cols in ((jnp.asarray(src), jnp.asarray(dst)),
             (jax.device_put(src, ROWS), jax.device_put(dst, ROWS))):
    placed, lims, _ = dp.place(inputs(*cols, n))
    assert all(c.dtype == jnp.int32 for c in placed["E"])
    assert lims == {}
    out = dp.run(inputs(*cols, n))
    check(out, src, dst, n)
# the same through the single-chip path
from repro.core import compile_program
check(compile_program(programs.pagerank).run(inputs(src, dst, n)),
      src, dst, n)
assert dp.faults.counters["descend"] == 0
assert "bailed:" not in dp.explain_rounds()
print("INT32_OK")
"""

_PLACE_CODE = _PRELUDE + """
n = 2**24 + 8
src, dst = graph(n, 201)          # 414 rows: not a multiple of 4
rows = len(src)
assert rows % 4
dp = compile_distributed(programs.pagerank, mesh, ("data",),
                         out_of_core="off")
# every concatenate place() builds holds at most one block's rows
sizes = []
real = jnp.concatenate
def spy(parts, *a, **k):
    out = real(parts, *a, **k)
    sizes.append(out.shape[0])
    return out
jnp.concatenate = spy
try:
    placed, lims, alims = dp.place(inputs(jnp.asarray(src),
                                          jnp.asarray(dst), n))
finally:
    jnp.concatenate = real
blk = -(-rows // 4)
assert sizes and max(sizes) <= blk, (sizes, blk)
assert lims == {"E": rows} and alims == {}
for col, ref in zip(placed["E"], (src, dst)):
    assert col.sharding.is_equivalent_to(ROWS, 1)
    shards = sorted(col.addressable_shards, key=lambda s: s.index[0].start)
    assert [s.data.shape[0] for s in shards] == [blk] * 4
    assert len({s.device for s in shards}) == 4
    host = np.concatenate([np.asarray(s.data) for s in shards])
    assert np.array_equal(host[:rows], ref) and not host[rows:].any()

# rows already in equal blocks on the mesh: taken as they lie, the same
# device buffers, whether evenly divisible or handed over as RowBlocks
def bufs(a):
    return sorted(s.data.unsafe_buffer_pointer() for s in a.addressable_shards)
cut = rows - rows % 4
even = jax.device_put(src[:cut], ROWS), jax.device_put(dst[:cut], ROWS)
padded = [RowBlocks(jax.device_put(np.concatenate(
    [c, np.zeros(4 * blk - rows, np.int32)]), ROWS), rows) for c in (src, dst)]
for cols, want in ((even, {}), (tuple(padded), {"E": rows})):
    placed, lims, _ = dp.place(inputs(*cols, n))
    assert lims == want
    for got, given in zip(placed["E"], cols):
        given = given.data if isinstance(given, RowBlocks) else given
        assert bufs(got) == bufs(given)
# and the padded bag runs to the same answer as the plain one
check(dp.run(inputs(*padded, n)), src, dst, n)
assert np.array_equal(np.asarray(padded[0]), src)
assert dp.faults.counters["descend"] == 0
print("PLACE_OK")
"""

_CLOCK_CODE = _PRELUDE + """
n = 64
rng = np.random.default_rng(3)
src = rng.integers(0, n, 400).astype(np.int32)
dst = rng.integers(0, n, 400).astype(np.int32)

class SteppedClock:
    # reads 10 ms apart, and one read 5 s late: to the old watchdog a
    # round that enqueued then looked 500x slower than the others
    def __init__(self):
        self.reads = 0
    def __call__(self):
        self.reads += 1
        return self.reads * 0.01 + (5.0 if self.reads >= 12 else 0.0)

for fused in (True, False):
    dp = compile_distributed(programs.pagerank, mesh, ("data",),
                             round_fusion=fused)
    dp.faults.clock = SteppedClock()
    for _ in range(4):
        out = dp.run(inputs(src, dst, n, steps=2))
    check(out, src, dst, n, steps=2)
    c = dp.faults.counters
    assert c["straggler"] == 0 and c["speculative"] == 0, dp.explain_faults()
    assert dp.faults.spec_saved_s == 0.0
print("CLOCK_OK")
"""

_TRACE_CODE = _PRELUDE + """
import tempfile
sys.path.insert(0, "bench")
import devtrace, progtrace
n = 64
rng = np.random.default_rng(4)
src = rng.integers(0, n, 402).astype(np.int32)      # pads to 404
dst = rng.integers(0, n, 402).astype(np.int32)
dp = compile_distributed(programs.pagerank, mesh, ("data",))
ins = inputs(src, dst, n, steps=2)
jax.block_until_ready(dp.run(ins))
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
try:
    for _ in range(2):
        jax.block_until_ready(dp.run(ins))
finally:
    jax.profiler.stop_trace()
pt = progtrace.load(devtrace.latest_xplane(d))
runs = [s for s in pt.spans if s[0] == "repro.run"]
assert len(runs) == 2, pt.spans
for _, lo, hi in runs:
    inner = [nm for nm, s, e in pt.spans if lo <= s and e <= hi
             and nm != "repro.run"]
    # place, then the out-degree round and the fused loop
    assert inner == ["repro.place", "repro.round", "repro.round"], inner
# the all-gather of P and the reduce-scatter of NP carry their scopes
rounds = []
call = dp._call_round
def keep(fn, args, *rest):
    rounds.append((fn, args))
    return call(fn, args, *rest)
dp._call_round = keep
dp._round_cache.clear()
jax.block_until_ready(dp.run(ins))
hlo = [fn.lower(*args).compile().as_text() for fn, args in rounds]
assert len(hlo) == 2
assert "Exchange:C" in hlo[0] and "Gather:" not in hlo[0]
assert "Gather:P" in hlo[1] and "Exchange:NP" in hlo[1]
print("TRACE_OK")
"""


def _run(code, marker):
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=_ROOT, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert marker in r.stdout


@pytest.mark.slow
def test_int32_ids_past_2_24_stay_exact():
    """PageRank through compile_distributed (and compile_program) on int32
    edge columns with N = 2**24 + 8 vertices: P within 1e-5 of a float64
    numpy PageRank, the out-degrees C exact."""
    _run(_INT32_CODE, "INT32_OK")


@pytest.mark.slow
def test_uneven_bag_placed_block_by_block():
    """A bag whose rows are not a multiple of 4 is padded block by block
    (no concatenate larger than one block); rows already in equal blocks,
    or handed over as RowBlocks, keep their device buffers."""
    _run(_PLACE_CODE, "PLACE_OK")


@pytest.mark.slow
def test_fault_free_run_never_speculates():
    """A fault-free run whose clock makes a round's enqueue look 500x slow
    flags no straggler and enqueues no speculative backup."""
    _run(_CLOCK_CODE, "CLOCK_OK")


@pytest.mark.slow
def test_run_spans_and_exchange_scopes():
    """`repro.run` holds `repro.place` and one `repro.round` per round;
    the fused round's HLO carries the `Gather:` and `Exchange:` scopes."""
    _run(_TRACE_CODE, "TRACE_OK")
