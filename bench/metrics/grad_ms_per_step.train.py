"""Device ms a step in the workers' forward and backward passes
(``phase.grad``, inclusive: its ``phase.data``), averaged over the
chips."""
from bench import phases


def read(data):
    return phases.ms_per_round(data, "grad")
