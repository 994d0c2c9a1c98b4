"""Front end and dispatch, the skew probe alone: the `repro.salt_probe`
spans of `CompiledProgram.run` in the traced window (`collect_salts`, with
its device-to-host copy of up to 4096 key rows), mean per job."""
import progtrace


def read(r):
    pt = progtrace.of(r)
    if pt is None:
        return None
    return 1e3 * progtrace.span_seconds(pt, "repro.salt_probe") / r.jobs
