"""Share of the traced window in which no operation ran on a chip (1
minus the union of its ``XLA Ops`` intervals), averaged over the
chips."""


def read(data):
    red = data["reduction"]
    if red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
