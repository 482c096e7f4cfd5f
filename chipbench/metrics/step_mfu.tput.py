"""The whole model's share of the chip's int8 peak: the true operations of
one row (every layer of the configuration, unpadded, as ``ops/<kind>.py``
counts them) times the rows run in the traced window, over the window and
the peak."""


def read(run):
    if run.trace is None or not run.counters["batched_rows"]:
        return None
    ops = run.model_ops_per_row * run.counters["batched_rows"]
    return 100.0 * ops / run.trace["window_s"] / run.peaks["int8_ops_per_s"]
