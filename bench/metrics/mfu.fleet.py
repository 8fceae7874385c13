"""The whole round's share of the chips' bf16 peak: LeNet FLOPs of local
training and in-scan evaluation per round (``bench.work``), times the
rounds executed in the traced window, over its length and the peak of
the cell's chips."""


def read(data):
    red = data["reduction"]
    if data["rounds"] <= 0 or red.window_s <= 0:
        return None
    flops = data["flops_per_round"] * data["rounds"]
    return 100.0 * flops / (red.window_s * data["chips"] * data["peaks"]["flops_bf16"])
