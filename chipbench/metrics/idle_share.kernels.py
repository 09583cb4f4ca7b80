"""Share of the traced window in which no operation ran on the device,
in a kernel cell, in percent."""


def read(r):
    t = r.trace
    return 100.0 * (1.0 - t.busy_s / t.window_s)
