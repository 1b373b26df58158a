"""The fused encode kernel's share of its roofline: the bytes its
algorithm must move (4 B per real nonzero in, the packed codes out) at
the chip's HBM bandwidth, over the kernel's device time in the trace.
No integer peak of the vector unit is published, so this share is a
memory bound only and understates a kernel bound by its hashing."""
from bench import counts
from bench.peaks import peaks

KERNEL = r"(minhash|oph)_pack_pallas"


def read(ctx):
    t, c = ctx["trace"], ctx["counters"]
    kernel_s = t.op_seconds(KERNEL)
    if kernel_s <= 0:
        return None
    nbytes = counts.encode_bytes(c["nnz"], c["docs"], c["k"], c["b"])
    floor = nbytes / peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * floor / kernel_s
