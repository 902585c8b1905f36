"""Model FLOP/s utilization of the traced window: the operations one decode
call needs (the family's ``decode_step``, each client's call counted once,
wasted and padded lanes not at all), over the traced window's length and
the chip's peak bf16 rate."""
from bench import flops


def read(run):
    if run.trace is None or run.peak is None:
        return None
    traced = [c for c in run.calls if c.traced]
    if not traced:
        return None
    step, hf = flops.family(run.spec).decode_step, run.spec["config"]
    ops = sum(step(hf, c.kv_len)[0] for c in traced)
    return 100.0 * ops / (run.trace["window_s"] * run.peak["bf16_flops_per_s"])
