"""Share of the roofline reached by the ``qdwconv`` kernel class: the least
time the chip needs for the class's true work (``work/qdwconv.py``) in the
traced window, over the device time of its ops there."""


def read(run):
    return run.roofline("qdwconv")
