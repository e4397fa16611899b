"""aggregate_roofline: the Pallas aggregate kernel's share of its
roofline.  Its time is the device time of the trace events of its kernel
(the Mosaic custom call ``aggregate.<n>``, named after
``kernels.mixing.ops.aggregate``); its bytes, per call, are the (n, P) payload
read once and the fp32 row written (``costs.traffic_model``, P
unpadded).  It is memory-bound: n flops per payload value is far below
the chip's ratio of FLOP/s to bytes/s."""

import sys

KERNEL = "aggregate"


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, calls = ctx.trace.kernel_s(KERNEL)
    if calls == 0 or seconds <= 0:
        print(f"aggregate_roofline: no Mosaic kernel {KERNEL!r} in the "
              "trace; the metric is left out", file=sys.stderr)
        return None
    least = ctx.system.aggregate_bytes_per_call() * calls \
        / ctx.peaks.hbm_bytes
    return 100.0 * least / seconds
