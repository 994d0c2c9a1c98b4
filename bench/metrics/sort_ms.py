"""Device time per job of the HLO sort ops, from the trace: the argsort
of the sort backend behind `_segment_flat`.  The gathers that apply its
order and the sorted scatter are fusions of their own and not counted."""
import re

import devtrace

SORT = re.compile(r"^sort(\.\d+)?$")     # the HLO sort op, not fusions


def read(r):
    if r.trace is None or not r.jobs:
        return None
    s = devtrace.op_seconds(r.trace, SORT)
    return 1e3 * s / r.jobs if s > 0 else None
