"""The bits kernel's share of its HBM roofline (kernels:
``kernels/bmv/bmv.py`` ``bits_pallas``): the bytes its calls need (tiles,
gathered operand words and outputs, from the bucket shapes by
``bench/lib/kbytes.py``) at the chip's HBM peak, over the kernel's time in
the trace. The kernel does one AND and one OR per word it reads, so HBM
bandwidth bounds it."""

#: the bits kernel's custom calls, named in a TPU trace after the jitted
#: wrappers that launch it (push, pull and count)
PATTERNS = (r'^%_bin_bin_(bin|bin_pull|full)\.\d+ = .*tpu_custom_call',)


def read(ctx):
    tr, nbytes = ctx.trace, ctx.facts.get("bits_bytes")
    if tr is None or not nbytes:
        return None
    t = tr.kernel_s(PATTERNS)
    if t <= 0:
        return None
    return nbytes / ctx.peaks["hbm_bw"] / t * 100.0
