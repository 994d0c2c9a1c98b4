# DIABLO-JAX: the paper's primary contribution — translation of array-based
# loops to distributed data-parallel programs — retargeted from Spark to JAX.
#
# Pipeline: @loop_program (Python-source frontend, paper Fig. 1 language)
#   → analysis.check (Def. 3.1 restrictions)
#   → translate (Fig. 2 rules E/K/D/U/S + Rule 2 unnesting)
#   → passes.plan_program (optimizer pipeline → physical-plan IR, plan.py:
#     Rules 16/17, einsum recognition, §5 tiled fusion, DSE, update fusion,
#     distribution analysis: dist_analysis.py infers a per-array sharding
#     REP ≤ ONED_VAR ≤ ONED_ROW ≤ TWOD_BLOCK — ONED_VAR marks bag-derived/
#     filtered arrays with variable live blocks, rebalanced to ONED_ROW
#     only where readers need it — printed by CompiledProgram.explain())
#   → lower.PlanExecutor (plan nodes → JAX, runtime guards + fallbacks)
#   → distributed (shard_map execution of the same plan over a mesh;
#     bags AND inferred-ONED_ROW dense arrays shard as row blocks)
from .analysis import check
from .chunked import ChunkLoop, ChunkRunner, chunk_plan, choose_chunk_rows
from .frontend import (bag, dim, intscalar, loop_program, map_, matrix,
                       parse_program, scalar, vector)
from .interp import run as interpret
from .loop_ast import Program, RejectionError
from .lower import CompiledProgram, PlanExecutor, compile_program
from .memest import MemEstimate, estimate, shape_env, shape_env_from_signature
from .passes import PlanConfig, plan_program
from .translate import translate

__all__ = ["loop_program", "parse_program", "compile_program", "interpret",
           "check", "translate", "CompiledProgram", "PlanExecutor",
           "PlanConfig", "plan_program", "Program",
           "RejectionError", "vector", "matrix", "map_", "bag", "dim",
           "scalar", "intscalar",
           "MemEstimate", "estimate", "shape_env", "shape_env_from_signature",
           "ChunkLoop", "ChunkRunner", "chunk_plan", "choose_chunk_rows"]
