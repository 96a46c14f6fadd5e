"""idle.apply: the share of the traced window in which no program ran on
the device (1 - busy / window).  Moves ``rot_gflops``."""


def read(view):
    r = view.reduction
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
