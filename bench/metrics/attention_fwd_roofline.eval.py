"""Roofline share of the attention forward in a rollout: the least time
of every attention layer the scan ran (the whole batch per live round)
over the device time under ``mrsch.kernel.mha_fwd``, in %."""
from harness import readers, work


def read(data):
    cfg = data["config"]
    if cfg["state_module"] != "attention" or "live_rounds" not in data:
        return None
    flops, nbytes = work.attention_cost(
        *work.attention_shape(cfg, data["batch_rows"]), work.itemsize(cfg))
    least = (data["live_rounds"] * cfg["attn_layers"]
             * work.least_time(flops, nbytes, data["peaks"]))
    return readers.roofline(data, "mha_fwd", least)
