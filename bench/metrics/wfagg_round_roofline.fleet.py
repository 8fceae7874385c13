"""Share of its roofline the WFAgg round kernel reaches: the least bytes
a round must move (``bench.work.wfagg_round_bytes``) over peak HBM
bandwidth, times the rounds in the traced window, over the kernel's
device time there.  Bytes bound it: the kernel's FLOPs are O(N K d)
compares and multiply-adds, under a hundredth of a FLOP per byte of
the chip's balance point."""


def read(data):
    red = data["reduction"]
    if not data["n_kernels"] or red.kernel_s <= 0 or data["rounds"] <= 0:
        return None
    least_s = data["bytes_per_round"] * data["rounds"] / data["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / red.kernel_s
