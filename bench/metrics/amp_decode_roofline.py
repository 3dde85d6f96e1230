"""The fused AMP decode kernel's share of its roofline
(``bench/readers.py``): (10 + 4 iters) s c operations per block, y in and
x-hat out (``bench/counts.py``), over the device time of its events."""
from bench import readers

#: how the kernel's events are named in the device trace: the pallas_call
#: has no name of its own, so its custom call takes the name of the jitted
#: wrapper around it (``amp_decode_fused.19``)
NAMES = (r"^amp_decode_fused",)


def read(ctx):
    return readers.kernel_roofline(ctx, NAMES, "amp")
