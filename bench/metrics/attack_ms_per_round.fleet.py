"""Device ms a round building the Byzantine rows (``phase.attack``)."""
from bench import phases


def read(data):
    return phases.ms_per_round(data, "attack")
