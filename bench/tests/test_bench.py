"""CPU checks of the benchmark harness: the contract of ``BENCHMARK.json``,
that every name it holds finds its file, the refusal without a chip, both
drivers end to end at a tiny size, the trace reduction, the operation
counts and the family contract, and that a cell, or a model family, is
added with new files alone."""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_benchmark_json_follows_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    names = set()
    for c in bench["configs"]:
        assert set(c) == CONFIG_KEYS
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
        names.add(c["name"])
    used = set()
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == CELL_KEYS
        for k in ("name", "config", "traffic"):
            assert NAME.match(w[k]), w[k]
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert w["config"] in names
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
    assert used == names
    cells = {w["name"] for w in bench["workloads"]}
    assert len(cells) == len(bench["workloads"])
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"])
        assert m["moves"] in e2e
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_name_finds_its_file(bench):
    for c in bench["configs"]:
        spec = json.loads((ROOT / c["file"]).read_text())
        assert spec["name"] == c["name"] and spec["source"] == c["source"]
        assert spec["reduced"] == c["reduced"]
        assert (BENCH / "models" / f"{spec['model']}.py").is_file()
    for w in bench["workloads"]:
        mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        assert (BENCH / "drivers" / f"{mix['driver']}.py").is_file()
        check = json.loads((BENCH / "checks" / f"{w['name']}.json")
                           .read_text())
        assert check["max_logit_gap"] > 0
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_run_without_a_chip_exits_nonzero(bench):
    cell = bench["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


# ---------------------------------------------------------------------------
# both drivers at a tiny size
# ---------------------------------------------------------------------------

def tiny_spec() -> dict:
    spec = json.loads((BENCH / "configs" / "qwen3-0.6b.json").read_text())
    spec["config"].update(hidden_size=64, intermediate_size=128,
                          num_attention_heads=4, num_key_value_heads=2,
                          head_dim=16, num_hidden_layers=2, vocab_size=256)
    spec["bucket_len"] = 64
    return spec


def tiny_mix(traffic: str, clients: int) -> dict:
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    mix.update(clients=clients, pool=8, max_total=63, check_requests=3)
    mix["prompt_len"].update(median=6, min=2, max=16)
    mix["output_len"].update(median=5, min=2, max=8)
    return mix


def run_tiny(bench, cell_name: str, traffic: str, clients: int,
             seconds: float = 1.0, trace: bool = False, **kw) -> dict:
    from bench import run

    cell = dict(next(w for w in bench["workloads"]
                     if w["name"] == cell_name))
    check = json.loads((BENCH / "checks" / f"{cell_name}.json").read_text())
    return run.run_cell(cell, tiny_spec(), tiny_mix(traffic, clients), check,
                        bench, 2**33 + 7, seconds, trace, require_tpu=False,
                        **kw)


@pytest.mark.parametrize("cell_name,traffic,clients", [
    ("qwen3-1.7b.chat1", "chat1", 1),
    ("qwen3-0.6b.edge8", "edge8", 3),
])
def test_driver_serves_and_checks(bench, cell_name, traffic, clients):
    res = run_tiny(bench, cell_name, traffic, clients)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert set(res["metrics"]) == e2e
    for m in res["metrics"].values():
        assert m["value"] > 0
    assert res["checks"]["max_logit_gap"]["value"] <= \
        res["checks"]["max_logit_gap"]["limit"]


def test_traffic_sizes_do_not_depend_on_the_seed():
    from bench.traffic import Traffic, load_mix

    mix = load_mix("edge8")
    a, b = Traffic(mix, 151936, 1), Traffic(mix, 151936, 2**40 + 3)
    for c in range(mix["clients"]):
        for i in range(5):
            ra, rb = a.request(c, i), b.request(c, i)
            assert ra.prompt.shape == rb.prompt.shape
            assert ra.new_tokens == rb.new_tokens
            assert ra.prompt.shape[1] + ra.new_tokens <= mix["max_total"]
            assert not (ra.prompt == rb.prompt).all()
    again = Traffic(mix, 151936, 1).request(3, 2)
    assert (again.prompt == a.request(3, 2).prompt).all()


def test_every_mix_names_its_source():
    for path in (BENCH / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        assert {"lengths", "loop"} <= set(mix["source"]), path.name
        assert isinstance(mix["assumed"], list)
        assert isinstance(mix["cuts"], list)


def test_every_client_with_a_finished_request_is_checked():
    import numpy as np

    from bench import run
    from bench.serving import RequestRecord

    reqs = [RequestRecord(np.zeros((1, 4 + i), np.int32), 2, 0.0, t_end=1.0,
                          tokens=[0] * (2 + c + i), client=c)
            for c in range(8) for i in range(4)]
    reqs.append(RequestRecord(np.zeros((1, 4), np.int32), 9, 0.0,
                              tokens=[0] * 50, client=8))    # unfinished
    for n in (1, 8, 12):
        picked = run.pick_checked(reqs, n, 2**33 + 1)
        assert picked[0] is reqs[31]                          # the longest
        assert {r.client for r in picked} == set(range(8))
        assert len({id(r) for r in picked}) == len(picked) == max(n, 8)
    again = run.pick_checked(reqs, 12, 2**33 + 1)
    assert [id(r) for r in again] == [id(r) for r in picked]


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in evs])
        for ln, evs in lines.items()])


def test_trace_reduce_busy_and_idle_on_known_intervals():
    from bench import trace_reduce

    host = _plane("/host:CPU", {"python": [
        ("bench.infer", 0, 1000), ("bench.absorb", 1000, 200),
        ("PjitFunction(step)", 100, 300),
    ]})
    dev = _plane("/device:TPU:0", {
        "XLA Ops": [("fusion.1", 100, 200), ("decode_attention.2", 250, 100),
                    ("fusion.1", 300, 100), ("rmsnorm", 900, 500)],
        "XLA Modules": [("jit_step", 0, 1200)],
    })
    out = trace_reduce.reduce_planes([host, dev])
    # window 0..1200 ns; busy = [100, 400) + [900, 1200) = 600 ns
    assert out["window_s"] == pytest.approx(1200e-9)
    assert out["busy_s"] == pytest.approx(600e-9)
    assert out["idle_share"] == pytest.approx(0.5)
    assert trace_reduce.kernel_seconds(out, "decode_attention") == \
        pytest.approx(100e-9)
    assert trace_reduce.kernel_seconds(out, "rmsnorm") == pytest.approx(300e-9)
    assert out["idle_by_host"] == {"bench.infer": pytest.approx(600e-9)}
    # a gap is put down to the innermost host event at its midpoint
    dev2 = _plane("/device:TPU:0", {"XLA Ops": [("fusion.1", 0, 150),
                                                ("fusion.2", 250, 700)]})
    out2 = trace_reduce.reduce_planes([host, dev2])
    assert out2["idle_by_host"] == {
        "PjitFunction(step)": pytest.approx(100e-9),
        "bench.absorb": pytest.approx(250e-9),
    }
    top = trace_reduce.top_ops(out, 2)
    assert {name for name, _ in top} == {"fusion.1", "rmsnorm"}
    assert [t for _, t in top] == pytest.approx([300e-9, 300e-9])


def test_trace_reduce_reads_a_recorded_trace(tmp_path):
    import time

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from bench import trace_reduce

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.infer"):
            f(x).block_until_ready()
            time.sleep(0.01)
        time.sleep(0.005)
    jax.profiler.stop_trace()
    planes = list(ProfileData.from_file(
        trace_reduce.find_xplane(str(tmp_path))).planes)
    spans = trace_reduce.host_annotations(planes)
    assert [n for n, _, _ in spans] == ["bench.infer"] * 3
    for _, s, e in spans:
        assert 0.01 <= e - s < 1.0
    # the CPU has no device plane: its ops run on host threads, so the same
    # reduction over a plane renamed as a device counts them as busy time
    cpu_ops = [p for p in planes if p.name == "/host:CPU"][0]
    fake = _plane("/device:TPU:0", {"XLA Ops": [
        (ev.name, ev.start_ns, ev.duration_ns)
        for ln in cpu_ops.lines if ln.name.startswith("tf_XLAPjRtCpuClient")
        for ev in ln.events if ev.duration_ns > 0]})
    out = trace_reduce.reduce_planes(planes + [fake])
    lo, hi = spans[0][1], spans[-1][2]
    assert out["window_s"] == pytest.approx(hi - lo)
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["idle_share"] > 0.5     # mostly asleep
    assert trace_reduce.reduce_planes(planes) is None


# ---------------------------------------------------------------------------
# operation and byte counts
# ---------------------------------------------------------------------------

def test_flops_by_hand():
    from bench import flops
    from bench.models import qwen3

    hf = {"hidden_size": 4, "intermediate_size": 6, "num_hidden_layers": 2,
          "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 2,
          "vocab_size": 10, "torch_dtype": "bfloat16"}
    # per layer: q 4x4, k 4x2, v 4x2, o 4x4, gate/up 4x6, down 6x4, norms
    # 4 + 4 + 2 + 2 -> 16 + 8 + 8 + 16 + 72 + 12 = 132; embedding 40 + 4
    assert qwen3.parameter_count(hf) == 2 * 132 + 40 + 4
    ops, nbytes = qwen3.decode_step(hf, kv_len=3)
    # per layer 2*(32 + 16 + 72) = 240 matmul ops + 4*2*2*3 = 48 attention
    assert ops == 2 * (240 + 48) + 2 * 4 * 10
    # weights once + per layer K and V (1 head x 2 x 2 bytes) x (3 read + 1)
    assert nbytes == (2 * 132 + 44) * 2 + 2 * (2 * 1 * 2 * 2) * 4
    assert qwen3.decode_attention(hf, 3) == (4 * 2 * 2 * 3,
                                             2 * 2 * 2 * 2 + 2 * 3 * 1 * 2 * 2)
    assert qwen3.rmsnorm_calls(hf) == [(1, 4), (2, 2), (1, 2), (1, 4)] * 2 \
        + [(1, 4)]
    assert qwen3.rmsnorm(hf, 2, 2) == (16.0, (2 * 2 * 2 + 2) * 2.0)
    peak = flops.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert flops.bound_seconds(197e12, 1.0, peak) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        flops.peaks("no such chip")


def test_qwen3_1_7b_parameter_count():
    from bench.models import qwen3

    spec = json.loads((BENCH / "configs" / "qwen3-1.7b.json").read_text())
    assert qwen3.parameter_count(spec["config"]) == pytest.approx(1.72e9,
                                                                  rel=0.01)


# Qwen3's counts as bench/flops.py computed them before they moved into the
# family module: (configuration, kv_len, decode_step's operations and bytes)
PARENT_STEPS = [
    ("qwen3-1.7b", 1, 3_441_131_520, 3_441_379_328),
    ("qwen3-1.7b", 77, 3_458_564_096, 3_450_095_616),
    ("qwen3-1.7b", 511, 3_558_113_280, 3_499_870_208),
    ("qwen3-0.6b", 77, 1_209_630_720, 1_201_045_504),
    ("qwen3-0.6b", 511, 1_309_179_904, 1_250_820_096),
]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("config,kv_len,ops,nbytes", PARENT_STEPS)
def test_family_decode_step_keeps_the_parent_counts(config, kv_len, ops,
                                                    nbytes):
    from bench import flops

    spec = _config(config)
    assert flops.family(spec).decode_step(spec["config"], kv_len) == \
        (ops, nbytes)


@pytest.mark.parametrize("config,params", [("qwen3-1.7b", 1_720_574_976),
                                           ("qwen3-0.6b", 596_049_920)])
def test_family_kernel_calls_keep_the_parent_counts(config, params):
    from bench import flops

    spec = _config(config)
    fam, hf = flops.family(spec), spec["config"]
    assert fam.parameter_count(hf) == params
    calls = fam.kernel_calls(hf, 77)
    assert set(calls) == {"decode_attention", "rmsnorm"}
    assert calls["decode_attention"] == [(28, 630_784.0, 323_584.0)]
    assert sum(n for n, _, _ in calls["rmsnorm"]) == 113


def _run_data(spec: dict, kv_lens, trace=None, peak=V5E):
    """A run whose traced calls are at ``kv_lens``, with one untraced call
    that no per-layer count may read."""
    from bench.serving import CallRecord, RunData

    calls = [CallRecord(0, 0.01, kv, True, 3, "replaying", True)
             for kv in kv_lens]
    calls.append(CallRecord(1, 0.01, 200, True, 3, "replaying", False))
    if trace is None:
        trace = {"window_s": 4.0, "busy_s": 1.0, "idle_share": 0.75,
                 "devices": 1, "idle_by_host": {},
                 "op_seconds": {"decode_attention.6": 2e-3,
                                "decode_attention.7": 1e-3,
                                "rmsnorm.3": 4e-4, "fusion.1": 1.0}}
    return RunData(spec=spec, calls=calls, requests=[], window_s=51.0,
                   setup={}, counters={}, trace=trace, peak=peak)


@pytest.mark.parametrize("config,kv_lens,step_ops,rmsnorm_bytes", [
    # chat1: one client, calls at growing lengths
    ("qwen3-1.7b", (1, 77, 511), (3_441_131_520, 3_458_564_096,
                                  3_558_113_280), 1_058_816),
    # edge8: a batched round's members, two at one length
    ("qwen3-0.6b", (77, 511, 511), (1_209_630_720, 1_309_179_904,
                                    1_309_179_904), 708_608),
])
def test_per_layer_readers_keep_the_parent_readings(config, kv_lens, step_ops,
                                                    rmsnorm_bytes):
    """The readers as the parent wrote them, by hand: both configurations
    have 28 layers of 16 query and 8 KV heads of 128, so a decode-attention
    call reads 8192 + 4096·kv_len bytes, and every one of its calls and of a
    token's 113 RMSNorm calls (``rmsnorm_bytes`` in all) is bound by its
    bytes on a v5e."""
    from bench import run

    data = _run_data(_config(config), kv_lens)
    want = {
        "mfu": 100 * sum(step_ops) / (4.0 * 197e12),
        "decode_attention_roofline":
            100 * 28 * sum(8192 + 4096 * kv for kv in kv_lens) / 819e9 / 3e-3,
        "rmsnorm_roofline": 100 * len(kv_lens) * rmsnorm_bytes / 819e9 / 4e-4,
    }
    for name, value in want.items():
        assert run.read_metric(name, data) == pytest.approx(value, rel=1e-9)


def test_kernel_roofline_reads_nothing_where_there_is_nothing():
    from bench import flops

    spec = _config("qwen3-0.6b")
    assert flops.kernel_roofline(_run_data(spec, (77,)), "rmsnorm") > 0
    no_kernel = _run_data(spec, (77,))
    no_kernel.trace["op_seconds"]["matmul_kernel.2"] = 1e-3
    for data, kernel in [
        (_run_data(spec, (77,), peak=None), "rmsnorm"),
        (dataclasses.replace(_run_data(spec, (77,)), trace=None), "rmsnorm"),
        (_run_data(spec, (77,)), "matmul_kernel"),   # no time in the trace
        (no_kernel, "matmul_kernel"),                # not the family's
        (_run_data(spec, ()), "rmsnorm"),            # no traced call
    ]:
        assert flops.kernel_roofline(data, kernel) is None


def test_every_family_keeps_the_contract(bench):
    """Every module in bench/models/ has the five functions, and each
    configuration's family gives finite positive counts at both ends of
    its cache bucket."""
    import math

    from bench import flops

    names = ("program_config", "make_params", "logit_stats", "decode_step",
             "kernel_calls")
    for path in (BENCH / "models").glob("*.py"):
        if path.name == "__init__.py":
            continue
        mod = importlib.import_module(f"bench.models.{path.stem}")
        for name in names:
            assert callable(getattr(mod, name, None)), (path.name, name)
    for c in bench["configs"]:
        spec = json.loads((ROOT / c["file"]).read_text())
        fam, hf = flops.family(spec), spec["config"]
        for kv_len in (1, spec["bucket_len"]):
            counts = list(fam.decode_step(hf, kv_len))
            calls = fam.kernel_calls(hf, kv_len)
            assert calls, c["name"]
            for entries in calls.values():
                assert entries
                for n, ops, nbytes in entries:
                    assert isinstance(n, int) and n > 0
                    counts += [ops, nbytes]
            assert all(math.isfinite(x) and x > 0 for x in counts), c["name"]


# ---------------------------------------------------------------------------
# a cell added with new files only
# ---------------------------------------------------------------------------

def _copy_with_cell(tmp_path, bench, model: str) -> dict:
    """Copy the benchmark under ``tmp_path`` and add the cell
    ``tiny.throwaway``: a tiny configuration of the family ``model``, a
    traffic mix and a check as new files, and new entries in (the returned
    copy of) BENCHMARK.json."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = tiny_spec()
    spec["name"] = "tiny"
    spec["model"] = model
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(spec))
    (tmp_path / "bench/traffic/throwaway.json").write_text(
        json.dumps(tiny_mix("chat1", 1)))
    (tmp_path / "bench/checks/tiny.throwaway.json").write_text(
        json.dumps({"max_logit_gap": 0.25}))
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": "tiny", "source": spec["source"],
                           "file": "bench/configs/tiny.json", "reduced": [],
                           "why": "throwaway"})
    new["workloads"].append({"name": "tiny.throwaway", "config": "tiny",
                             "traffic": "throwaway", "chips": 1,
                             "why": "throwaway"})
    return new


def _run_copy(tmp_path, new: dict, script: str) -> dict:
    """Write the copy's BENCHMARK.json, run ``script`` (which prints one
    JSON line) against the copy on the CPU, and check that no file the
    benchmark already had was edited."""
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    head = ("import json, sys\n"
            f"sys.path[:0] = [{str(tmp_path)!r}, {str(ROOT / 'src')!r}]\n"
            "from bench import run\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", head + script],
                          cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    before = {p.relative_to(BENCH): p.read_bytes()
              for p in BENCH.rglob("*") if p.is_file()
              and "__pycache__" not in p.parts}
    for rel, data in before.items():
        assert (tmp_path / "bench" / rel).read_bytes() == data
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_a_cell_is_added_with_new_files_only(tmp_path, bench):
    """Copy the benchmark, add a throwaway configuration, traffic mix,
    check and per-layer metric as new files plus new entries in
    BENCHMARK.json, and run the new cell with no existing file edited."""
    new = _copy_with_cell(tmp_path, bench, "qwen3")
    (tmp_path / "bench/metrics/calls_in_window.py").write_text(
        "def read(run):\n    return len(run.calls)\n")
    new["per_layer"].append({"name": "calls_in_window", "unit": "calls",
                             "better": "higher", "source": "host_clock",
                             "layer": "client protocol",
                             "moves": "call_p50_ms",
                             "workloads": ["tiny.throwaway"]})
    res = _run_copy(tmp_path, new, (
        "bench, cell, spec, mix, check = run.cell_files('tiny.throwaway')\n"
        "res = run.run_cell(cell, spec, mix, check, bench, 5, 1.0, True,\n"
        "                   require_tpu=False)\n"
        "print(json.dumps(res))\n"))
    assert res["correct"] is True
    assert res["metrics"]["calls_in_window"]["value"] > 0


THROWAWAY_FAMILY = '''"""Qwen3's program, weights and reference, with every
operation and byte count doubled."""
from bench.models import qwen3
from bench.models.qwen3 import logit_stats, make_params, program_config


def decode_step(hf, kv_len):
    ops, nbytes = qwen3.decode_step(hf, kv_len)
    return 2 * ops, 2 * nbytes


def kernel_calls(hf, kv_len):
    return {k: [(2 * n, ops, nbytes) for n, ops, nbytes in calls]
            for k, calls in qwen3.kernel_calls(hf, kv_len).items()}
'''


def test_a_family_is_added_with_new_files_only(tmp_path, bench):
    """A second family joins as a new module with counts of its own, its
    cell named in ``mfu``'s workloads: the cell runs, and ``mfu`` and the
    kernel rooflines read that family's counts (here twice Qwen3's) on the
    run's own traced calls."""
    new = _copy_with_cell(tmp_path, bench, "throwaway")
    (tmp_path / "bench/models/throwaway.py").write_text(THROWAWAY_FAMILY)
    mfu = next(m for m in new["per_layer"] if m["name"] == "mfu")
    mfu["workloads"].append("tiny.throwaway")
    out = _run_copy(tmp_path, new, (
        "import dataclasses\n"
        "from bench import flops\n"
        "seen = []\n"
        "read = run.read_metric\n"
        "run.read_metric = lambda name, data: seen.append(data) or \\\n"
        "    read(name, data)\n"
        "bench, cell, spec, mix, check = run.cell_files('tiny.throwaway')\n"
        "res = run.run_cell(cell, spec, mix, check, bench, 5, 1.0, True,\n"
        "                   require_tpu=False)\n"
        "trace = {'window_s': 1.0, 'op_seconds': {'decode_attention.1': 1e-3,\n"
        "                                         'rmsnorm.2': 1e-3}}\n"
        "data = dataclasses.replace(seen[-1], trace=trace,\n"
        "                           peak=flops.peaks('TPU v5 lite'))\n"
        "as_qwen3 = dataclasses.replace(data, spec={**data.spec,\n"
        "                                           'model': 'qwen3'})\n"
        "readings = {label: [read('mfu', d),\n"
        "                    flops.kernel_roofline(d, 'decode_attention'),\n"
        "                    flops.kernel_roofline(d, 'rmsnorm')]\n"
        "            for label, d in (('family', data), ('qwen3', as_qwen3))}\n"
        "print(json.dumps({\n"
        "    'correct': res['correct'], 'readings': readings,\n"
        "    'traced': sum(c.traced for c in data.calls),\n"
        "    'module': flops.family(spec).__file__,\n"
        "    'per_layer': [m['name'] for m in\n"
        "                  run.metric_names(bench, 'tiny.throwaway', True)]}))\n"))
    assert out["correct"] is True
    assert out["traced"] > 0
    assert out["module"] == str(tmp_path / "bench/models/throwaway.py")
    assert "mfu" in out["per_layer"]
    family, qwen3 = out["readings"]["family"], out["readings"]["qwen3"]
    assert all(q > 0 for q in qwen3)
    assert family == pytest.approx([2 * q for q in qwen3], rel=1e-12)


def test_metric_readers_import_nothing_of_the_program():
    for path in (BENCH / "metrics").glob("*.py"):
        text = path.read_text()
        assert "repro" not in text, path.name
        spec = importlib.util.spec_from_file_location("m", path)
        assert spec is not None


def test_no_reader_names_a_model_key(bench):
    """A model's shapes are read in its family module alone: no metric
    reader, and not bench/flops.py, names a key of a configuration's
    published ``config``."""
    keys = set()
    for c in bench["configs"]:
        keys |= set(json.loads((ROOT / c["file"]).read_text())["config"])
    for path in [*(BENCH / "metrics").glob("*.py"), BENCH / "flops.py"]:
        text = path.read_text()
        named = {k for k in keys if f'"{k}"' in text or f"'{k}'" in text}
        assert not named, (path.name, sorted(named))
