"""Device time per job of the group-by's ops: those whose named scope has
a `SegmentReduce:<dest>` part (`core/lower.py` `_exec_segment`, and the
Pallas kernel with its layout work), whatever backend op_select picks.
0 when the program's ops carry plan-node scopes but none of a group-by."""
import progtrace


def read(r):
    pt = progtrace.of(r)
    if pt is None or not progtrace.scoped(pt):
        return None
    s = sum(v for (node, _), v in progtrace.by_stage(pt).items()
            if node.startswith("SegmentReduce:"))
    return 1e3 * s / r.jobs
