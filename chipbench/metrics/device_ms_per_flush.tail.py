"""Device busy time per flush in the traced window."""


def read(run):
    if run.trace is None or not run.counters["batches"]:
        return None
    return 1e3 * run.trace["busy_s"] / run.counters["batches"]
