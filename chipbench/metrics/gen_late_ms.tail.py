"""How late the load generator sent: the 99th percentile of send time
minus due time, over the requests due inside the traced sub-window (the
profiler's own start and stop stall the host just outside it)."""


def read(run):
    from chipbench.bench import percentile

    rec = run.rec
    lo, hi = run.host_span
    inside = (rec["start"] >= lo) & (rec["start"] < hi)
    if not inside.any():
        return None
    return 1e3 * percentile((rec["sent"] - rec["start"])[inside], 99)
