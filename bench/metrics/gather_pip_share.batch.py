"""The gather-PIP kernel's percent of the device's busy time in the batch
window: the trace time of its ops over busy time.  On the chip the Pallas
call's op is named after the function that issues it,
``crossings_candidates`` (kernels/gather_pip.py); the kernel body's name
is matched too."""
from benchlib import readers

NAMES = ("crossings_candidates", "_gather_pip_kernel")


def read(ctx):
    return readers.kernel_share(ctx, NAMES)
