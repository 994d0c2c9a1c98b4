"""Render failure-ledger goldens for the chaos CI artifact.

Drives the compiled group_by program and a small PlanServer through one
scripted scenario per degradation-ladder level (DESIGN.md §11) and
writes every ``explain_faults()`` / ``explain_serving()`` rendering to
the path given on the command line.  The artifact makes ledger-text
regressions diffable across CI runs without re-running the job.

  PYTHONPATH=src python tools/fault_goldens.py FAULT_ledgers.txt
"""
from __future__ import annotations

import sys

import numpy as np


def _inputs(seed=0, n=40):
    r = np.random.default_rng(seed)
    return dict(S=(r.integers(0, 10, n).astype(np.float64),
                   r.standard_normal(n)), C=np.zeros(10))


def _fresh_cp():
    from repro.core import compile_program
    from repro.core.programs import ALL
    return compile_program(ALL["group_by"])


def scenarios():
    from repro.core import faults as F

    def clean():
        cp = _fresh_cp()
        cp.run(_inputs())
        return cp.explain_faults()

    def transient_retry():
        cp = _fresh_cp()
        cp.faults.sleep = lambda s: None
        with F.inject(F.FaultSpec("lower.whole_trace", "transient", nth=1)):
            cp.run(_inputs())
        return cp.explain_faults()

    def deterministic_descent():
        cp = _fresh_cp()
        cp.faults.sleep = lambda s: None
        with F.inject(F.FaultSpec("lower.whole_trace", "deterministic",
                                  nth=1)):
            cp.run(_inputs())
        return cp.explain_faults()

    def capacity_chunked():
        from repro.core import compile_program
        from repro.core.programs import ALL
        cp = compile_program(ALL["group_by"], op_select="force:scatter")
        cp.faults.sleep = lambda s: None
        with F.inject(F.FaultSpec("lower.whole_trace", "capacity", nth=1,
                                  times=10 ** 4)):
            cp.run(_inputs())
        return cp.explain_faults() + "\n" + cp.explain_chunked()

    def interp_oracle():
        cp = _fresh_cp()
        cp.faults.sleep = lambda s: None
        with F.inject(F.FaultSpec("lower.node", "transient", nth=1,
                                  times=10 ** 4)):
            cp.run(_inputs())
        return cp.explain_faults()

    def serve_chaos():
        from repro.serve import PlanServer
        srv = PlanServer({"group_by": _fresh_cp()}, max_batch=8)
        srv.faults.sleep = lambda s: None
        srv.policy.backoff_s = 0.0
        specs = [F.FaultSpec("serve.batched_call", "transient", nth=1),
                 F.FaultSpec("serve.batched_call", "deterministic",
                             rid=3, times=10 ** 4),
                 F.FaultSpec("serve.stack", "poison", rid=5,
                             times=10 ** 4)]
        ts = [srv.submit("group_by", _inputs(i)) for i in range(8)]
        with F.inject(*specs):
            srv.drain()
        states = ",".join(t.state for t in ts)
        return (srv.explain_serving() + "\n" + srv.explain_faults()
                + f"\nticket states: {states}")

    def shard_loss_recovered():
        import jax
        from repro.core import compile_program
        from repro.core.distributed import compile_distributed
        from repro.core.programs import ALL
        from repro.launch.mesh import make_test_mesh
        ndev = len(jax.devices())
        if ndev < 4:                  # forced to 4 in __main__; imported
            return f"(skipped: {ndev} device(s), scenario needs 4)"
        mesh = make_test_mesh((4,), ("data",))
        cp = compile_program(ALL["pagerank"], round_fusion=False)
        cp.policy.backoff_s = 0.0
        cp.policy.max_backoff_s = 0.0
        cp.faults.sleep = lambda s: None
        dp = compile_distributed(cp, mesh)
        r = np.random.default_rng(7)
        nn = 16
        ins = dict(E=(r.integers(0, nn, 60).astype(np.float64),
                      r.integers(0, nn, 60).astype(np.float64)),
                   P=np.full(nn, 1.0 / nn), NP=np.zeros(nn),
                   C=np.zeros(nn), N=nn, num_steps=3.0, steps=0.0,
                   b=0.85)
        dp.run(ins)                   # warm traces: the golden is the
        #                               ledger, not compile-time retries
        with F.inject(F.FaultSpec("dist.shard_lost", kind="shard_lost",
                                  nth=7, shard=2)):
            dp.run(ins)
        return dp.explain_faults()

    def speculative_backup_win():
        class Clock:                  # deterministic injected time — the
            def __init__(self):      # golden must not depend on the wall
                self.t = 0.0

            def __call__(self):
                return self.t

            def advance(self, dt):
                self.t += dt

        from repro.serve import PlanServer
        clk = Clock()
        srv = PlanServer({"group_by": _fresh_cp()}, max_batch=1,
                         clock=clk)
        srv.faults.sleep = lambda s: None
        srv.policy.backoff_s = 0.0
        specs = [F.FaultSpec("serve.batched_call", "slow", nth=1,
                             times=5, delay_s=0.01),
                 F.FaultSpec("serve.batched_call", "slow", nth=6,
                             delay_s=1.0)]
        with F.inject(*specs, clock=clk):
            for i in range(6):
                srv.submit("group_by", _inputs(i, 20))
                srv.drain()
        return srv.explain_serving() + "\n" + srv.explain_faults()

    return [("clean run (no faults)", clean),
            ("transient at lower.whole_trace: retried in place",
             transient_retry),
            ("deterministic at lower.whole_trace: one descent to eager",
             deterministic_descent),
            ("capacity at lower.whole_trace: out-of-core chunked rung",
             capacity_chunked),
            ("persistent transient at lower.node: interpreter oracle",
             interp_oracle),
            ("serve chaos: retry + bisection + poisoned lane",
             serve_chaos),
            ("shard lost mid-loop: lineage recovery, no ladder descent",
             shard_loss_recovered),
            ("straggling flush: speculative backup copy wins",
             speculative_backup_win)]


def main() -> None:
    out = sys.argv[1] if len(sys.argv) > 1 else "FAULT_ledgers.txt"
    chunks = []
    for title, fn in scenarios():
        chunks.append(f"=== {title} ===\n{fn()}\n")
    text = "\n".join(chunks)
    with open(out, "w") as f:
        f.write(text)
    print(text)
    print(f"[fault_goldens] wrote {out}")


if __name__ == "__main__":
    import os
    # before jax loads: the shard-loss scenario needs a 4-way mesh of
    # forced host devices, so the goldens always come from the CPU backend
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, "src")
    main()
