"""Share of the traced window in which the device was idle while the host
was inside `CompiledProgram.run` (the `repro.run` span), mean over the
chips used.  The rest of `device_idle_pct` is the harness's own time and
the completion wait."""
import progtrace


def read(r):
    pt = progtrace.of(r)
    if pt is None:
        return None
    lo, hi = pt.window
    return 100.0 * progtrace.run_idle_seconds(pt) / ((hi - lo) * 1e-9)
