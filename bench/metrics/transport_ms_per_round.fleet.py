"""Device ms a round in the chaos transport (``phase.transport``,
inclusive: its ``phase.sanitize``): crash freeze, the stacked ring
matrix and its re-keyed tables, the ring shift, the served-lag
re-keying."""
from bench import phases


def read(data):
    return phases.ms_per_round(data, "transport")
