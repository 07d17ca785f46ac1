"""Model step (`models/`): the decode step's share of its roofline. The
least time of each traced decode-only engine step (the larger of its
FLOPs over the chip's bf16 peak and its bytes over the HBM bandwidth:
weights once, plus the keys and values of the positions each active lane
has in use), summed, over the device busy time inside those steps'
spans."""


def read(rec):
    tr = rec.trace
    if not tr or not tr.get("decode_device_s"):
        return None
    dev = sum(tr["decode_device_s"])
    if dev <= 0:
        return None
    return 100.0 * sum(tr["decode_least_s"]) / dev
