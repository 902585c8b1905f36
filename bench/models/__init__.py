"""Model families: one module each, ``bench/models/<model>.py``.

A configuration file names its family under ``model``; the harness imports
that module (``bench.flops.family``) and nothing else knows a model's
shapes.  Each family module provides five functions; the reference and the
counts take nothing of the program's making:

* ``program_config(spec)``: the program's config for the configuration
  file ``spec``;
* ``make_params(spec, seed)``: the program's weights from a seed, made on
  the device in one jitted call;
* ``logit_stats(spec, weights, tokens, picks, *, fp8=False)``: the plain
  reference over one sequence (``fp8``: the control);
* ``decode_step(hf, kv_len) -> (ops, bytes)``: one token of one client
  through the whole model, ``hf`` being the configuration's published
  ``config``: the real vocabulary (not a padded table), keys and values up
  to the valid length ``kv_len`` (not the whole cache bucket), every weight
  read once.  A matrix product of (m, k) by (k, n) is 2·m·k·n operations.
  ``mfu`` reads it;
* ``kernel_calls(hf, kv_len) -> {kernel: [(count, ops, bytes), ...]}``: for
  one token, every call of each kernel that the family's step launches,
  keyed by the name its ops carry in the trace
  (``bench.trace_reduce.kernel_seconds``), each entry ``count`` calls of
  ``ops`` operations and ``bytes`` bytes.  ``bench.flops.kernel_roofline``
  reads it; a kernel the family does not launch is left out.
"""
