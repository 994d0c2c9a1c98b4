"""Share of the HBM roofline a group-by job reaches: the least time its
necessary bytes take at the chip's peak bandwidth, over the device-busy
time per job.  The count depends on N and K alone, whatever backend
op_select picks."""


def necessary_bytes(rows: int, groups: int) -> int:
    """N rows of a float32 key and a float32 value read once; K float32
    destinations read and written once."""
    return 8 * rows + 8 * groups


def read(r):
    if r.trace is None or not r.jobs or "rows" not in r.work \
            or r.trace.busy_s <= 0 or "hbm_bytes_per_s" not in r.peaks:
        return None
    least_s = necessary_bytes(r.work["rows"], r.work["groups"]) \
        / r.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (r.trace.busy_s / r.jobs)
