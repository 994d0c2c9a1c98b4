"""One chip: `compile_program(...).run()`, the whole-program path."""
from __future__ import annotations


class Runner:
    def __init__(self, job, chips: int, autotune_cache: str):
        from repro.core import compile_program, programs
        # an autotune cache path that never exists: op_select decides from
        # the committed cost model alone, never from a stray local file
        self.cp = compile_program(getattr(programs, job.PROGRAM),
                                  autotune_cache=autotune_cache)

    def run(self, inputs: dict) -> dict:
        return self.cp.run(inputs)

    def compiles(self) -> int:
        """Whole-program traces so far (each one an XLA compile or a
        persistent-cache load)."""
        return self.cp.trace_count

    def rungs(self) -> tuple:
        """Ladder state: any change across a job means it took a rung
        (descent, retry, whole-program trace failure)."""
        c = self.cp.faults.counters
        return (c["descend"], c["retry"], self.cp.faults.level_reached,
                self.cp.trace_failures)

    def explain(self) -> str:
        return self.cp.explain() + "\n" + self.cp.explain_faults()
