"""Device ms a round synthesizing each batch's images and labels on the
device (``phase.data``, inside local training)."""
from bench import phases


def read(data):
    return phases.ms_per_round(data, "data")
