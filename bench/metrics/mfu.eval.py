"""Policy-network FLOPs the evaluation window required (one forward per
decision taken, from the configuration's logical shapes) over the traced
window times the chip's bf16 peak, in %."""
from harness import readers


def read(data):
    return readers.rollout_mfu(data)
