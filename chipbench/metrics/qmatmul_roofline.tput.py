"""Share of the roofline reached by the ``qmatmul`` kernel class: the least
time the chip needs for the class's true work (``work/qmatmul.py``) in the
traced window, over the device time of its ops there."""


def read(run):
    return run.roofline("qmatmul")
