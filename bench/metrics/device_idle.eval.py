"""Share of the traced evaluation window in which no operation ran on the
device (profiler trace: 1 - busy union / window), in %."""
from harness import readers


def read(data):
    return readers.idle_share(data)
