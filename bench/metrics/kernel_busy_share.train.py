"""Share of the busy time the WFAgg round kernel (``wfagg_round_indexed``,
run whole on every chip) takes, on the chip with the most busy time."""


def read(data):
    if not data["n_kernels"] or not data["per_device"]:
        return None
    busy, kernel = max(data["per_device"])
    if busy <= 0 or kernel <= 0:
        return None
    return 100.0 * kernel / busy
