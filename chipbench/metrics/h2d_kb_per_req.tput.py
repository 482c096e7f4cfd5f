"""KiB staged and copied to the device per answered request: the bucket
rows sent times the bytes of one row in the executable's entry layout
(``exec_plan.entry_shape``), over the requests completed, in the traced
window."""


def read(run):
    c = run.counters
    if not c["completed"]:
        return None
    return c["bucket_rows"] * run.entry_bytes / c["completed"] / 1024.0
