"""Share of the bucket rows sent to the device that carried a request
(``ModelMetrics`` ``batched_rows / bucket_rows``), over the traced window."""


def read(run):
    c = run.counters
    return 100.0 * c["batched_rows"] / c["bucket_rows"] if c["bucket_rows"] else None
