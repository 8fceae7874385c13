"""Device ms a round in aggregation (``phase.aggregate``, inclusive: the
round kernel and its ``phase.sanitize``): WFAgg-T re-keying and
bands, the round kernel, the history push, the final models."""
from bench import phases


def read(data):
    return phases.ms_per_round(data, "aggregate")
