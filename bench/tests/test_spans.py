"""CPU checks of the program-span reduction (``bench/spans.py``): span
times and idle attribution on known intervals, and the ``rrto.*`` spans a
recorded trace of both tiny drivers holds, nested where the program opens
them."""
from __future__ import annotations

import importlib

import pytest

from bench.tests.test_bench import _plane, tiny_mix, tiny_spec


def test_span_times_on_known_intervals():
    from bench import spans

    host = _plane("/host:CPU", {
        "python": [
            ("bench.infer", 100, 1000),
            ("rrto.intercept", 150, 800),
            ("rrto.replay", 200, 600),
            ("rrto.launch", 250, 100),
            ("PjitFunction(step)", 260, 80),        # JAX: not subtracted
            ("rrto.fetch", 400, 300),
            ("np.asarray(jax.Array)", 410, 280),
            ("bench.absorb", 1100, 100),
            ("rrto.intercept", 1150, 300),          # clipped at 1200
            ("rrto.adopt", 1190, 50),               # clipped at 1200
            ("rrto.adopt", 1300, 10),               # after the window
            ("rrto.launch", 50, 20),                # before the window
        ],
        "other thread": [("rrto.launch", 300, 100)],  # no harness span
    })
    out = spans.span_times([host], 100e-9, 1200e-9)
    assert set(out) == {"rrto.intercept", "rrto.replay", "rrto.launch",
                        "rrto.fetch", "rrto.adopt"}
    ns = pytest.approx
    assert out["rrto.intercept"] == {"count": 2, "total_s": ns(850e-9),
                                     "self_s": ns(240e-9)}
    # 800 - 600 (replay) on the first, 50 - 10 (clipped adopt) on the second
    assert out["rrto.replay"] == {"count": 1, "total_s": ns(600e-9),
                                  "self_s": ns(200e-9)}
    assert out["rrto.launch"] == {"count": 1, "total_s": ns(100e-9),
                                  "self_s": ns(100e-9)}
    assert out["rrto.fetch"]["self_s"] == ns(300e-9)
    assert out["rrto.adopt"] == {"count": 1, "total_s": ns(10e-9),
                                 "self_s": ns(10e-9)}


def test_idle_by_span_names_the_innermost_program_span():
    from bench import spans

    host = _plane("/host:CPU", {"python": [
        ("bench.round", 0, 1000),
        ("rrto.batch", 100, 600),
        ("rrto.batch_unstack", 300, 300),
        ("np.asarray(jax.Array)", 310, 280),   # JAX event: ignored
        ("DevicePut", 750, 100),               # JAX event under bench.round
    ]})
    dev = _plane("/device:TPU:0", {"XLA Ops": [
        ("fusion.1", 0, 100), ("while.3", 200, 100), ("eq.1", 700, 300)]})
    out = spans.idle_by_span([host, dev], 0.0, 1000e-9)
    # gaps: [100, 200) in rrto.batch, [300, 700) mid 500 in batch_unstack
    assert out == {"rrto.batch": pytest.approx(100e-9),
                   "rrto.batch_unstack": pytest.approx(400e-9)}
    full = spans.reduce_spans([host, dev])
    assert full["idle_by_span"] == out
    assert full["spans"]["rrto.batch"]["self_s"] == pytest.approx(300e-9)
    assert spans.reduce_spans([dev]) is None


# (span, the span it opens in) on each driver's steady calls
SOLO = {
    ("rrto.intercept", "bench.infer"),
    ("rrto.replay", "rrto.intercept"),
    ("rrto.fresh_upload", "rrto.replay"),
    ("rrto.launch", "rrto.replay"),
    ("rrto.fetch", "rrto.replay"),
}
EDGE = {
    ("rrto.round_prepare", "bench.round"),
    ("rrto.intercept", "bench.round"),
    ("rrto.batch", "rrto.intercept"),
    ("rrto.batch_params_check", "rrto.batch"),
    ("rrto.batch_stack", "rrto.batch"),
    ("rrto.launch", "rrto.batch"),
    ("rrto.batch_unstack", "rrto.batch"),
    ("rrto.adopt", "rrto.intercept"),
}
# a client that begins a request replays alone that round
EDGE_SOLO_ROUNDS = SOLO - {("rrto.intercept", "bench.infer")}


def _parents(planes):
    """(name, parent name) of every rrto.* event on the harness's thread."""
    from bench import spans, trace_reduce

    out = set()
    for line in spans._annotated_lines(planes):
        evs = trace_reduce._spans(
            line, lambda n: n.startswith(("rrto.", "bench.")))
        stack = []
        for name, s, e in sorted(evs, key=lambda x: (x[1], -x[2])):
            while stack and stack[-1][1] <= s:
                stack.pop()
            if name.startswith("rrto."):
                assert stack, f"{name} outside every harness annotation"
                out.add((name, stack[-1][0]))
            stack.append((name, e))
    return out


@pytest.mark.parametrize("driver,traffic,clients,required,allowed", [
    ("solo", "chat1", 1, SOLO, SOLO),
    ("edge", "edge8", 3, EDGE, EDGE | EDGE_SOLO_ROUNDS),
])
def test_recorded_trace_holds_the_program_spans(tmp_path, driver, traffic,
                                                clients, required, allowed):
    from jax.profiler import ProfileData

    from bench import spans, trace_reduce
    from bench.models import qwen3
    from bench.serving import CompileCounter, Profiler
    from bench.traffic import Traffic

    spec, mix = tiny_spec(), tiny_mix(traffic, clients)
    seed = 2**33 + 11
    compiles = CompileCounter()
    d = importlib.import_module(f"bench.drivers.{driver}").Driver(
        qwen3.program_config(spec), qwen3.make_params(spec, seed),
        Traffic(mix, spec["config"]["vocab_size"], seed), spec["bucket_len"],
        lambda msg: None, compiles)
    try:
        d.build()
        d.warm_up()
        d.run_window(1.0, Profiler(str(tmp_path), 60.0))
    finally:
        compiles.close()
    planes = list(ProfileData.from_file(
        trace_reduce.find_xplane(str(tmp_path))).planes)
    found = _parents(planes)
    assert required <= found <= allowed, sorted(found)
    times = spans.reduce_spans(planes)["spans"]
    calls = sum(1 for c in d.calls if c.traced)
    assert times["rrto.intercept"]["count"] == calls
    for t in times.values():
        assert 0 <= t["self_s"] <= t["total_s"] + 1e-12
