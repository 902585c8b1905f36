from repro.kernels.decode_attention.ops import (
    decode_attention,
    decode_attention_path,
    decode_attention_ref,
)
from repro.kernels.decode_attention.ref import decode_attention_q8_ref, quantize_kv
