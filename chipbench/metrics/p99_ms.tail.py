"""The 99th percentile of client latency (from the due time in an open loop,
from the send in a closed one) over the requests started inside the traced
sub-window. It is set by how many host stalls of 120 ms or more fall into
the window, which varies too much from run to run for a bound, so it is
read here beside the judged ``p90_ms``."""


def read(run):
    from chipbench.bench import MISSED_S, percentile

    rec = run.rec
    lo, hi = run.host_span
    inside = (rec["start"] >= lo) & (rec["start"] < hi)
    if not inside.any():
        return None
    lat = (rec["end"] - rec["start"]).copy()
    lat[~rec["ok"]] = MISSED_S
    return 1e3 * percentile(lat[inside], 99)
