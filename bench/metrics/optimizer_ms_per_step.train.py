"""Device ms a step in AdamW and the aggregated gradient's norm
(``phase.optimizer``), averaged over the chips."""
from bench import phases


def read(data):
    return phases.ms_per_round(data, "optimizer")
