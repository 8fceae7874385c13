"""Share of the device's busy time in ops that no ``phase.*`` scope
holds: the scan's own bookkeeping and copies XLA adds.  A scope dropped
from the program, or a fusion that moves work out of its phase, shows
here."""
from bench import phases


def read(data):
    return phases.unscoped_share(data)
