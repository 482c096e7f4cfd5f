"""Mean wait of a request in the batcher's queue, from admission to the
flush that takes it (the ``queue`` stage of a ``repro.obs.Tracer``)."""


def read(run):
    if run.queue_wait_us is None:
        return None
    return run.queue_wait_us / 1e3
