"""rmsnorm kernel: the least time its calls in the traced window could take
(bench/flops.py, the real rows of each call) over the kernel's device time
in the trace."""
from bench import flops, trace_reduce


def read(run):
    if run.trace is None or run.peak is None:
        return None
    spent = trace_reduce.kernel_seconds(run.trace, "rmsnorm")
    if spent <= 0:
        return None
    hf = run.spec["config"]
    per_call = sum(flops.bound_seconds(*flops.rmsnorm(hf, rows, width),
                                       run.peak)
                   for rows, width in flops.rmsnorm_calls(hf))
    n = sum(1 for c in run.calls if c.traced)
    return 100.0 * per_call * n / spent
