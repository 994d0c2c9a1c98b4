"""Host time per job of the program's `repro.place` spans in the traced
window: `DistributedProgram.place`, which takes bag columns already in row
blocks on the mesh as they lie.  None on a program without the span."""
import progtrace

SPAN = "repro.place"


def read(r):
    pt = progtrace.of(r)
    if pt is None or not any(n == SPAN for n, _, _ in pt.spans):
        return None
    return 1e3 * progtrace.span_seconds(pt, SPAN) / r.jobs
