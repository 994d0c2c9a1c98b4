"""Device time per job of the sort backend's permutation gathers: the ops
under a `SegmentReduce:<dest>/permute` scope (`vals[order]`, `ids[order]`
in `core/lower.py` `_segment_flat`).  0, not None, once a program that
carries the scopes no longer runs them."""
import progtrace


def read(r):
    pt = progtrace.of(r)
    if pt is None or not progtrace.scoped(pt):
        return None
    s = sum(v for (node, stage), v in progtrace.by_stage(pt).items()
            if node.startswith("SegmentReduce:")
            and stage.split("/")[0] == "permute")
    return 1e3 * s / r.jobs
