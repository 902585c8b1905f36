"""CPU checks of the benchmark harness: the contract of ``BENCHMARK.json``,
that every name it holds finds its file, the refusal without a chip, both
drivers end to end at a tiny size, the trace reduction and the operation
counts, and that a cell is added with new files alone."""
from __future__ import annotations

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

    hf = {"hidden_size": 4, "intermediate_size": 6, "num_hidden_layers": 2,
          "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 2,
          "vocab_size": 10, "torch_dtype": "bfloat16"}
    # per layer: q 4x4, k 4x2, v 4x2, o 4x4, gate/up 4x6, down 6x4, norms
    # 4 + 4 + 2 + 2 -> 16 + 8 + 8 + 16 + 72 + 12 = 132; embedding 40 + 4
    assert flops.parameter_count(hf) == 2 * 132 + 40 + 4
    ops, nbytes = flops.decode_step(hf, kv_len=3)
    # per layer 2*(32 + 16 + 72) = 240 matmul ops + 4*2*2*3 = 48 attention
    assert ops == 2 * (240 + 48) + 2 * 4 * 10
    # weights once + per layer K and V (1 head x 2 x 2 bytes) x (3 read + 1)
    assert nbytes == (2 * 132 + 44) * 2 + 2 * (2 * 1 * 2 * 2) * 4
    assert flops.decode_attention(hf, 3) == (4 * 2 * 2 * 3,
                                             2 * 2 * 2 * 2 + 2 * 3 * 1 * 2 * 2)
    assert flops.rmsnorm_calls(hf) == [(1, 4), (2, 2), (1, 2), (1, 4)] * 2 \
        + [(1, 4)]
    assert flops.rmsnorm(hf, 2, 2) == (16.0, (2 * 2 * 2 + 2) * 2.0)
    peak = flops.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert flops.bound_seconds(197e12, 1.0, peak) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        flops.peaks("no such chip")


def test_qwen3_1_7b_parameter_count():
    from bench import flops

    spec = json.loads((BENCH / "configs" / "qwen3-1.7b.json").read_text())
    assert flops.parameter_count(spec["config"]) == pytest.approx(1.72e9,
                                                                  rel=0.01)


# ---------------------------------------------------------------------------
# a cell added with new files only
# ---------------------------------------------------------------------------

def test_a_cell_is_added_with_new_files_only(tmp_path, bench):
    """Copy the benchmark, add a throwaway configuration, traffic mix,
    check and per-layer metric as new files plus new entries in
    BENCHMARK.json, and run the new cell with no existing file edited."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = tiny_spec()
    spec["name"] = "tiny"
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(spec))
    (tmp_path / "bench/traffic/throwaway.json").write_text(
        json.dumps(tiny_mix("chat1", 1)))
    (tmp_path / "bench/checks/tiny.throwaway.json").write_text(
        json.dumps({"max_logit_gap": 0.25}))
    (tmp_path / "bench/metrics/calls_in_window.py").write_text(
        "def read(run):\n    return len(run.calls)\n")
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": "tiny", "source": spec["source"],
                           "file": "bench/configs/tiny.json", "reduced": [],
                           "why": "throwaway"})
    new["workloads"].append({"name": "tiny.throwaway", "config": "tiny",
                             "traffic": "throwaway", "chips": 1,
                             "why": "throwaway"})
    new["per_layer"].append({"name": "calls_in_window", "unit": "calls",
                             "better": "higher", "source": "host_clock",
                             "layer": "client protocol",
                             "moves": "call_p50_ms",
                             "workloads": ["tiny.throwaway"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    before = {p.relative_to(BENCH): p.read_bytes()
              for p in BENCH.rglob("*") if p.is_file()
              and "__pycache__" not in p.parts}
    for rel, data in before.items():
        assert (tmp_path / "bench" / rel).read_bytes() == data
    script = (
        "import json, sys\n"
        f"sys.path[:0] = [{str(tmp_path)!r}, {str(ROOT / 'src')!r}]\n"
        "from bench import run\n"
        "bench, cell, spec, mix, check = run.cell_files('tiny.throwaway')\n"
        "res = run.run_cell(cell, spec, mix, check, bench, 5, 1.0, True,\n"
        "                   require_tpu=False)\n"
        "print(json.dumps(res))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["metrics"]["calls_in_window"]["value"] > 0


def test_metric_readers_import_nothing_of_the_program():
    for path in (BENCH / "metrics").glob("*.py"):
        text = path.read_text()
        assert "repro" not in text, path.name
        spec = importlib.util.spec_from_file_location("m", path)
        assert spec is not None
