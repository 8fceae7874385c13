"""Device ms a round in the in-scan evaluation (``phase.evaluate``):
per-node test accuracy, the benign mean and R²."""
from bench import phases


def read(data):
    return phases.ms_per_round(data, "evaluate")
