"""Share of the chips' busy time in ops that no ``phase.*`` scope holds:
the state's sharding constraints, copies XLA adds.  A scope dropped
from the step, or a fusion that moves work out of its phase, shows
here."""
from bench import phases


def read(data):
    return phases.unscoped_share(data)
