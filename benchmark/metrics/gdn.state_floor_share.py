"""How much of a decode step the linear layers' state costs at the least:
the bytes the traced decode dispatches had to read and write of it (every
slot's row of every linear layer, once each way, a dispatch; the
reference's ``state_bytes_per_slot``) over the chip's HBM bandwidth, as a
share of those dispatches' device time. The state's own floor, not a
kernel's roofline: the step also reads the weights and the pages."""
from benchmark.metrics._common import stream_programs


def read(ctx):
    count = getattr(ctx["cell"].reference(), "state_bytes_per_slot", None)
    decode, _ = stream_programs(ctx["trace"])
    if count is None or not decode:
        return None
    busy = ctx["trace"].busy_within(decode)
    if not busy:
        return None
    moved = 2 * ctx["config"]["engine"]["slots"] * count(ctx["config"]) \
        * len(decode)
    return 100.0 * moved / ctx["peaks"]["hbm_bytes_per_s"] / busy
