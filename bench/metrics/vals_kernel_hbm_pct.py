"""The value kernel's achieved HBM bandwidth as a share of the peak
(kernels: ``kernels/bmv/bmv.py`` ``vals_pallas``): the bytes its calls need
(tiles, gathered operand values, outputs, from the bucket shapes by
``bench/lib/kbytes.py``) over the kernel's time in the trace, against the
chip's HBM peak. Not a roofline share: the kernel's 32 bit-plane passes
bound it on the vector unit, whose integer peak v5e does not publish."""

#: the value kernel's custom calls, named in a TPU trace after the jitted
#: wrapper that launches it
PATTERNS = (r'^%_bin_full_full\.\d+ = .*tpu_custom_call',)


def read(ctx):
    tr, nbytes = ctx.trace, ctx.facts.get("vals_bytes")
    if tr is None or not nbytes:
        return None
    t = tr.kernel_s(PATTERNS)
    if t <= 0:
        return None
    return nbytes / ctx.peaks["hbm_bw"] / t * 100.0
