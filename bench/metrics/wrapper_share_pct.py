"""Share of the device's busy time spent outside the Pallas kernels (kernel
wrappers: ``kernels/bmv/ops.py``, ``kernels/spmm/ops.py``): gathers,
padding, scatter-merges and the loop's own operations."""

#: every Pallas kernel: Mosaic custom calls, as a TPU trace names them
PALLAS = (r'custom_call_target="tpu_custom_call"',)


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    busy = tr.busy_s()
    kern = tr.kernel_s(PALLAS)
    if busy <= 0 or kern <= 0:
        return None
    return (busy - kern) / busy * 100.0
