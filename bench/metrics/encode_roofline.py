"""Encode's share of the HBM roofline: the necessary bytes of every
``bench.encode`` call (raw pages in, serialized blobs out) over the device
time inside those spans, against the chip's HBM bandwidth."""


def read(t):
    spans = t.named("bench.encode")
    busy = t.busy_s(spans) if spans else 0.0
    if busy <= 0 or "encode_bytes" not in t.work:
        return None
    return 100.0 * len(spans) * t.work["encode_bytes"] / t.peaks["hbm_bytes_s"] / busy
