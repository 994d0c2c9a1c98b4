"""Device time per job of the collectives: the ops `devtrace.COLLECTIVE`
names (all-gather, reduce-scatter, all-reduce, all-to-all,
collective-permute, their async halves and the fusions XLA names after
them), mean over the chips.  It reads op names alone, so a program without
the `Gather:`/`Exchange:` scopes reads too; None when no collective ran."""
import devtrace


def read(r):
    if r.trace is None or not r.jobs:
        return None
    s = devtrace.op_seconds(r.trace, devtrace.COLLECTIVE)
    return 1e3 * s / r.jobs if s > 0 else None
