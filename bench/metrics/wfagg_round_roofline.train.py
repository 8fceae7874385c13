"""Share of its roofline the WFAgg round kernel reaches in the training
step: the least bytes of one aggregation (K candidates and K previous
candidates read, the aggregate written; ``bench.train_work``) at peak
HBM bandwidth, over the kernel's device time a step summed over the
chips.  The launch runs whole on every chip, so while it does this
reads 100 / K % at most."""


def read(data):
    if not data["n_kernels"] or data["rounds"] <= 0:
        return None
    kernel_s = sum(k for _, k in data["per_device"])
    if kernel_s <= 0:
        return None
    least_s = data["bytes_per_step"] * data["rounds"] / data["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
