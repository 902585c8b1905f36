"""decode_attention kernel: the least time its calls in the traced window
could take on this chip (the family's ``kernel_calls``, keys and values up
to each call's valid length) over the kernel's device time in the trace."""
from bench import flops


def read(run):
    return flops.kernel_roofline(run, "decode_attention")
