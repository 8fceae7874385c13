"""The whole step's share of the chips' bf16 peak: the model FLOPs of
every worker's forward and backward pass (``bench.train_work``, no
recomputation counted), times the steps executed in the traced window,
over its length and the peak of the cell's chips."""


def read(data):
    red = data["reduction"]
    if data["rounds"] <= 0 or red.window_s <= 0:
        return None
    flops = data["flops_per_step"] * data["rounds"]
    return 100.0 * flops / (red.window_s * data["chips"] * data["peaks"]["flops_bf16"])
