"""Device ms a round in local training (``phase.local_train``, inclusive:
its ``phase.data``): every node's minibatch SGD and the ravel of the
trained models."""
from bench import phases


def read(data):
    return phases.ms_per_round(data, "local_train")
