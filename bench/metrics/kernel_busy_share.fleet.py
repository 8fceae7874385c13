"""Share of the device's busy time spent in Mosaic kernels (the
``tpu_custom_call`` operations of the timed program)."""


def read(data):
    red = data["reduction"]
    if not data["n_kernels"] or red.busy_s <= 0 or red.kernel_s <= 0:
        return None
    return 100.0 * red.kernel_s / red.busy_s
