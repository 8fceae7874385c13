"""Device ms a step in collective operations (all-gather, all-reduce,
reduce-scatter, collective-permute, all-to-all; the union of their
intervals on a chip, averaged over the chips)."""


def read(data):
    red = data["reduction"]
    if data["rounds"] <= 0 or red.collective_s <= 0:
        return None
    return 1e3 * red.collective_s / data["rounds"]
