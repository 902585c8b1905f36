"""rmsnorm kernel: the least time its calls in the traced window could take
(the family's ``kernel_calls``, the real rows of each call) over the
kernel's device time in the trace."""
from bench import flops


def read(run):
    return flops.kernel_roofline(run, "rmsnorm")
