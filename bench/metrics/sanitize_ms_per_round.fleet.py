"""Device ms a round zeroing non-finite rows and demoting their edges
(``phase.sanitize``, summed over both sites: the transport's stacked
matrix and ``wfagg.sanitize_round``)."""
from bench import phases


def read(data):
    return phases.ms_per_round(data, "sanitize")
