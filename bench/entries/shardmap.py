"""A mesh of the cell's chips: `compile_distributed(...).run()` in shardmap
mode over one axis `data`, the path of a deployment whose bag is sharded.
The job's bag columns arrive in row blocks on that mesh."""
from __future__ import annotations


class Runner:
    def __init__(self, job, chips: int, autotune_cache: str):
        from repro.core import programs
        from repro.core.distributed import compile_distributed
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh((chips,), ("data",))
        # an autotune cache path that never exists (op_select decides from
        # the committed cost model alone); out-of-core off, so a capacity
        # fault fails the job at once instead of streaming the bag from
        # the host
        self.dp = compile_distributed(
            getattr(programs, job.PROGRAM), mesh, ("data",),
            mode="shardmap", autotune_cache=autotune_cache,
            out_of_core="off")
        self.bails = 0

    def run(self, inputs: dict) -> dict:
        out = self.dp.run(inputs)
        if "bailed:" in self.dp.explain_rounds():
            self.bails += 1             # a fused round fell back this job
        return out

    def compiles(self) -> int:
        """shard_map rounds traced so far (each one an XLA compile or a
        persistent-cache load)."""
        return self.dp._round_traces

    def rungs(self) -> tuple:
        """Any change across a job means it took a rung: a ladder
        descent, a retry, a straggler or a speculative copy, or a fused
        round that bailed."""
        c = self.dp.faults.counters
        return (c["descend"], c["retry"], c["straggler"], c["speculative"],
                self.dp.faults.level_reached, self.bails)

    def explain(self) -> str:
        return self.dp.explain_rounds() + "\n" + self.dp.explain_faults()
