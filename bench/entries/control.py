"""The control of a cell's comparison, in the program's place: the job's
plain reference computed in the precision below the program's
(`jobs/<job>.control`, bfloat16 on the device).  Its runs have to read
`correct` false; the benchmark's own runs never use it."""
from __future__ import annotations


class Runner:
    def __init__(self, job, chips: int, autotune_cache: str):
        self.job = job

    def run(self, inputs: dict) -> dict:
        return self.job.control(inputs)

    def compiles(self) -> int:
        return 0

    def rungs(self) -> tuple:
        return ()

    def explain(self) -> str:
        return "control: the reference in bfloat16"
