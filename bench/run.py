#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json``; its configuration is
``bench/configs/<config>.json``, whose ``model`` names its family module
``bench/models/<model>.py`` (program config, weights, reference, and the
operation and byte counts the per-layer metrics read), its traffic mix
``bench/traffic/<traffic>.json``, whose ``driver`` names
``bench/drivers/<driver>.py``, and every metric is read by
``bench/metrics/<metric>.py``.  A run makes its weights and requests from
``--seed``, warms up every shape the window uses (set-up), serves the mix for
``--seconds`` (the window), then checks the served tokens against the plain
reference (``correct``).  With ``--trace 1`` the first seconds of the window
are profiled and the per-layer metrics are printed instead of the end-to-end
ones.  The last line of standard output is one JSON object.  The run exits
nonzero, with no result, when JAX finds no TPU or fewer chips than the cell
asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_SECONDS = 4.0   # how much of the window a --trace 1 run profiles


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def cell_files(name: str) -> tuple:
    """(cell entry, configuration, traffic mix, check) for a cell name."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    spec = load_json(BENCH / "configs" / f"{cell['config']}.json")
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    check = load_json(BENCH / "checks" / f"{name}.json")
    return bench, cell, spec, mix, check


def metric_names(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The cell's end-to-end metrics (trace 0) or per-layer ones (trace 1)."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metric(name: str, run) -> Optional[float]:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(run)
    return None if value is None else float(value)


def device_report(jax) -> dict:
    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs]
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                 for s in stats),
    }


def check_chip(jax, chips: int) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")


def pick_checked(requests, n: int, seed: int) -> list:
    """The requests whose served tokens are checked: the longest finished
    one, then one finished request of every other client that has one, then
    more up to ``n`` in all; all but the longest drawn from the seed."""
    done = [r for r in requests if r.t_end is not None and not r.failed]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), r.prompt.shape[1]))
    rng = np.random.default_rng([seed, 3])
    picked = [longest]
    for client in sorted({r.client for r in done} - {longest.client}):
        own = [r for r in done if r.client == client]
        picked.append(own[int(rng.integers(len(own)))])
    rest = [r for r in done if not any(r is p for p in picked)]
    more = rng.choice(len(rest), size=max(0, min(n - len(picked), len(rest))),
                      replace=False)
    return picked + [rest[i] for i in sorted(more)]


def decide(gap: float, checked: int, short: int, limit: float) -> bool:
    """``correct``: some requests were checked, each has all its tokens, and
    the widest gap is within the limit."""
    return checked > 0 and short == 0 and gap <= limit


def served_gaps(model, spec: dict, weights, requests, bucket_len: int,
                controls: bool = False) -> Dict[str, float]:
    """For each checked request, run the reference over its prompt and
    served tokens; return the widest gap by which a served token's logit
    lies below the reference's largest (and, with ``controls``, the same
    for the token the fp8 control puts first at each position)."""
    out = {"served": 0.0, "control": 0.0, "tokens": 0}
    for r in requests:
        p = r.prompt.shape[1]
        seq = np.concatenate([r.prompt[0], np.asarray(r.tokens[:-1])])
        tokens = np.zeros(bucket_len, np.int32)
        tokens[: seq.size] = seq
        rows = np.arange(p - 1, p - 1 + len(r.tokens))
        picks = np.zeros((2 if controls else 1, bucket_len), np.int32)
        picks[0, rows] = r.tokens
        if controls:
            _, _, ctrl = model.logit_stats(spec, weights, tokens,
                                           picks[:1], fp8=True)
            picks[1, rows] = ctrl[rows]
        best, picked, _ = model.logit_stats(spec, weights, tokens, picks)
        gaps = best[None, rows] - picked[:, rows]
        out["served"] = max(out["served"], float(gaps[0].max()))
        if controls:
            out["control"] = max(out["control"], float(gaps[1].max()))
        out["tokens"] += len(r.tokens)
    return out


def run_cell(cell: dict, spec: dict, mix: dict, check: dict, bench: dict,
             seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, controls: bool = False) -> dict:
    """One run of one cell; returns the result line's object."""
    import jax

    if require_tpu:
        check_chip(jax, int(cell["chips"]))
        from repro.launch.serve import configure_compile_cache

        cache = configure_compile_cache()
        # every program of the run is kept, so only a cell's first run in a
        # checkout compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        log(f"compile cache {cache}")
    from bench import flops
    from bench.serving import CompileCounter, Profiler, RunData
    from bench.traffic import Traffic

    model = flops.family(spec)
    driver_mod = importlib.import_module(f"bench.drivers.{mix['driver']}")
    compiles = CompileCounter()
    kind = jax.devices()[0].device_kind
    peak = flops.peaks(kind) if require_tpu else None

    t = time.perf_counter()
    cfg = model.program_config(spec)
    params = model.make_params(spec, seed)
    weights_s = time.perf_counter() - t
    traffic = Traffic(mix, spec["config"]["vocab_size"], seed)
    driver = driver_mod.Driver(cfg, params, traffic, spec["bucket_len"], log,
                               compiles)
    del params
    t = time.perf_counter()
    driver.build()
    build_s = time.perf_counter() - t
    t = time.perf_counter()
    driver.warm_up()
    warm_s = time.perf_counter() - t
    record_s = sum({c.round_id: c.host_s for c in driver.setup_calls
                    if c.mode == "recording"}.values())
    compile_s = driver.setup_compile_s
    log(f"set-up: weights {weights_s:.3f} s, clients built and weights "
        f"uploaded {build_s:.3f} s, warm-up {warm_s:.3f} s (recording "
        f"{record_s:.3f} s, compiling calls {compile_s:.3f} s) "
        f"host wall")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    profiler = Profiler(trace_dir, min(TRACE_SECONDS, seconds)) if trace \
        else None
    counters0 = driver.counters()
    compiles0 = dict(compiles.counts)
    # set-up's objects (recorded calls, traced programs) leave the cyclic
    # collector's view, so its full passes do not stall the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_PROCESS
    window_s = driver.run_window(seconds, profiler)
    gc.unfreeze()
    counters1 = driver.counters()
    window_compiles = {k: v - compiles0.get(k, 0)
                       for k, v in compiles.counts.items()
                       if v != compiles0.get(k, 0)}
    counters = {k: counters1[k] - counters0.get(k, 0) for k in counters1}
    compiles.close()
    log(f"window: {window_s:.3f} s, {len(driver.calls)} calls, "
        f"{len(driver.requests)} requests begun; program counters over the "
        f"window {counters}; compile events in the window "
        f"{window_compiles or 0}")
    driver.finish_first_tokens()
    device = device_report(jax)

    requests = [r for r in driver.requests if r.counted]
    attempted = len(requests)
    failed = sum(r.failed for r in requests)
    checked = pick_checked(driver.requests, int(mix["check_requests"]), seed)
    short = sum(len(r.tokens) != r.new_tokens for r in checked)
    calls = driver.calls
    driver.close()
    del driver
    gc.collect()

    t = time.perf_counter()
    weights = model.make_params(spec, seed)
    gaps = served_gaps(model, spec, weights, checked, spec["bucket_len"],
                       controls=controls)
    del weights
    log(f"reference: {len(checked)} requests, {gaps['tokens']} served "
        f"tokens checked in {time.perf_counter() - t:.3f} s host wall")
    limit = float(check["max_logit_gap"])
    checks = {
        "max_logit_gap": {"value": gaps["served"] if checked else None,
                          "limit": limit},
        "short_requests": {"value": short, "limit": 0},
    }
    correct = decide(gaps["served"], len(checked), short, limit)

    reduced = None
    if trace:
        from bench import trace_reduce

        reduced = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is not None:
            log(f"trace: window {reduced['window_s']:.4f} s, device busy "
                f"{reduced['busy_s']:.4f} s over {reduced['devices']} "
                f"device(s), {len(reduced['op_seconds'])} distinct ops")
    run = RunData(
        spec=spec, calls=calls, requests=requests, window_s=window_s,
        setup={"setup_s": setup_s, "record_s": record_s,
               "compile_s": compile_s},
        counters=counters, trace=reduced, peak=peak,
    )
    metrics = {}
    for m in metric_names(bench, cell["name"], trace):
        value = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace and reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(reduced),
            "idle_gaps": trace_reduce.idle_gaps(reduced),
        }
    if controls:
        # the control in the program's place: the same requests, the same
        # decision
        result["control_gap"] = gaps["control"]
        result["control_correct"] = decide(gaps["control"], len(checked), 0,
                                           limit)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    bench, cell, spec, mix, check = cell_files(args.workload)
    import jax

    try:
        check_chip(jax, int(cell["chips"]))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    result = run_cell(cell, spec, mix, check, bench, args.seed, args.seconds,
                      bool(args.trace))
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
