"""The forward projection kernel's share of its roofline
(``bench/readers.py``): 2 s c operations per block, x in and y out
(``bench/counts.py``), over the device time of its events."""
from bench import readers

#: how the kernel's events are named in the device trace: the pallas_call
#: has no name of its own, so its custom call takes the name of the jitted
#: wrapper around it (``vmap_jit_ota_project__.19``); the adjoint's
#: (``ota_project_t``) is not this kernel
NAMES = (r"ota_project(?!_t)",)


def read(ctx):
    return readers.kernel_roofline(ctx, NAMES, "project")
