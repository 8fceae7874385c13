"""Device ms a step in the robust aggregation (``phase.aggregate``): the
candidates' concatenation and gather, the WFAgg round kernel, the
history push and the split back into leaves, averaged over the chips."""
from bench import phases


def read(data):
    return phases.ms_per_round(data, "aggregate")
