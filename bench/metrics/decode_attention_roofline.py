"""decode_attention kernel: the least time its calls in the traced window
could take on this chip (bench/flops.py, keys and values up to each call's
valid length) over the kernel's device time in the trace."""
from bench import flops, trace_reduce


def read(run):
    if run.trace is None or run.peak is None:
        return None
    spent = trace_reduce.kernel_seconds(run.trace, "decode_attention")
    if spent <= 0:
        return None
    hf = run.spec["config"]
    bound = sum(
        hf["num_hidden_layers"] * flops.bound_seconds(
            *flops.decode_attention(hf, c.kv_len), run.peak)
        for c in run.calls if c.traced
    )
    return 100.0 * bound / spent
