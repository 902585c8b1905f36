import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
)
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell and record the roofline source data.

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi

Per cell this produces results/dryrun/<arch>__<shape>__<mesh>.json with:
    memory_analysis   bytes per device (argument/output/temp/generated)
    cost_analysis     XLA HLO flops / bytes-accessed / transcendentals
    collectives       per-op-kind byte totals parsed from the compiled HLO
    status            ok | failed (+ traceback)
"""

import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import CONFIGS, SHAPES
from repro.configs.base import ArchConfig, ShapeConfig
from repro.distributed.sharding import translate_tree
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh, mesh_dp_size
from repro.models.registry import (
    batch_specs,
    decode_specs,
    get_model,
    params_shape,
    shape_applies,
)
from repro.training.optimizer import init_opt_state, opt_state_specs
from repro.training.step import make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")

_COLLECTIVE_RE = re.compile(
    r"=\s*(?P<shape>[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, Any]:
    """Sum output-shape bytes of every collective instruction (per device)."""
    per_kind: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        kind = m.group("op")
        b = _shape_bytes(m.group("shape"))
        per_kind[kind] = per_kind.get(kind, 0.0) + b
        counts[kind] = counts.get(kind, 0) + 1
    return {
        "bytes_by_kind": per_kind,
        "counts": counts,
        "total_bytes": sum(per_kind.values()),
    }


def _fit(spec: P, struct, mesh) -> NamedSharding:
    """Drop sharding axes whose size does not divide the dimension — jit
    argument/output shardings require exact divisibility; replication is the
    safe fallback (hillclimb revisits the hot cells)."""
    sizes = dict(mesh.shape)
    parts = list(spec)
    parts += [None] * (len(struct.shape) - len(parts))
    out = []
    for dim, ax in zip(struct.shape, parts):
        if ax is None:
            out.append(None)
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        total = 1
        for a in axes:
            total *= sizes[a]
        out.append(ax if (dim > 0 and dim % total == 0) else None)
    return NamedSharding(mesh, P(*out))


def _sharding_tree(spec_tree, mesh, struct_tree=None):
    translated = translate_tree(spec_tree, mesh.axis_names)
    if struct_tree is None:
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            translated,
            is_leaf=lambda s: isinstance(s, P),
        )
    return jax.tree.map(
        lambda s, st: _fit(s, st, mesh),
        translated,
        struct_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def batch_shardings(batch_struct, mesh):
    dp = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
    return jax.tree.map(
        lambda leaf: _fit(P(dp, *([None] * (len(leaf.shape) - 1))), leaf, mesh),
        batch_struct,
    )


def _strip_tp(tree):
    def strip(spec):
        return P(*(None if a == "tp" else a for a in spec))

    return jax.tree.map(strip, tree, is_leaf=lambda s: isinstance(s, P))


def lower_cell(cfg: ArchConfig, shape: ShapeConfig, mesh) -> Dict[str, Any]:
    model = get_model(cfg)
    dp = mesh_dp_size(mesh)
    p_struct = params_shape(cfg)
    p_specs = model.param_specs(cfg)
    if cfg.disable_tp:
        p_specs = _strip_tp(p_specs)
    p_shard = _sharding_tree(p_specs, mesh, p_struct)
    rep = NamedSharding(mesh, P())

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            train_step = make_train_step(cfg, remat=True)
            opt_struct = jax.eval_shape(init_opt_state, p_struct)
            opt_shard = _sharding_tree(
                opt_state_specs(p_specs, p_struct, dp), mesh, opt_struct
            )
            b_struct = batch_specs(cfg, shape)
            b_shard = batch_shardings(b_struct, mesh)
            metrics_shard = {"loss": rep, "grad_norm": rep, "step": rep}
            fn = jax.jit(
                train_step,
                in_shardings=(p_shard, opt_shard, b_shard),
                out_shardings=(p_shard, opt_shard, metrics_shard),
            )
            lowered = fn.lower(p_struct, opt_struct, b_struct)
        elif shape.kind == "prefill":
            b_struct = batch_specs(cfg, shape)
            b_shard = batch_shardings(b_struct, mesh)
            eff_seq = (
                min(shape.seq_len, cfg.max_target_positions)
                if cfg.is_encoder_decoder
                else shape.seq_len
            )
            eff_shape = shape
            cache_struct = jax.eval_shape(
                lambda: model.init_cache(cfg, shape.global_batch, eff_seq)
            )
            cache_shard = _sharding_tree(
                model.cache_specs(cfg, shape.global_batch, dp), mesh, cache_struct
            )

            def prefill_fn(params, batch):
                return model.prefill(params, batch, cfg, eff_seq)

            fn = jax.jit(
                prefill_fn,
                in_shardings=(p_shard, b_shard),
                out_shardings=(rep, cache_shard),
            )
            lowered = fn.lower(p_struct, b_struct)
        else:  # decode
            token_s, cache_struct, pos_s = decode_specs(cfg, shape)
            dp_axes = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
            tok_shard = NamedSharding(
                mesh,
                P(dp_axes if shape.global_batch % dp == 0 else None, None),
            )
            cache_shard = _sharding_tree(
                model.cache_specs(cfg, shape.global_batch, dp), mesh, cache_struct
            )

            def serve_step(params, token, cache, pos):
                return model.decode_step(params, token, cache, pos, cfg)

            fn = jax.jit(
                serve_step,
                in_shardings=(p_shard, tok_shard, cache_shard, rep),
                out_shardings=(tok_shard, cache_shard),
            )
            lowered = fn.lower(p_struct, token_s, cache_struct, pos_s)

        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    weighted = analyze_hlo(hlo)  # trip-count-aware flops/bytes/collectives

    mem_dict = {}
    if mem is not None:
        for k in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
            "alias_size_in_bytes",
        ):
            mem_dict[k] = getattr(mem, k, None)
    cost_dict = {}
    if cost:
        for k in ("flops", "bytes accessed", "transcendentals", "utilization operand 0"):
            if k in cost:
                cost_dict[k] = float(cost[k])
        # keep everything numeric and small
        for k, v in cost.items():
            if isinstance(v, (int, float)) and len(cost_dict) < 40:
                cost_dict.setdefault(k, float(v))

    return {
        "compile_seconds": compile_s,
        "memory_analysis": mem_dict,
        "cost_analysis": cost_dict,
        "collectives_unweighted": coll,
        "hlo_weighted": weighted,
        "hlo_bytes": len(hlo),
    }


def run_cell(
    arch: str, shape_name: str, mesh_kind: str, out_dir: str,
    force: bool = False, overrides: Optional[Dict[str, Any]] = None,
    tag: str = "",
) -> str:
    import dataclasses as _dc

    cfg = CONFIGS[arch]
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    out_path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}{suffix}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            prev = json.load(f)
        if prev.get("status") == "ok":
            return f"SKIP (cached ok) {out_path}"

    record: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "n_devices": 512 if mesh_kind == "multi" else 256,
    }
    if not shape_applies(cfg, shape):
        record["status"] = "skipped"
        record["reason"] = f"{shape_name} not applicable to {arch} (DESIGN.md §Arch-applicability)"
    else:
        try:
            mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
            record.update(lower_cell(cfg, shape, mesh))
            record["status"] = "ok"
        except Exception as e:  # noqa: BLE001 - record and continue
            record["status"] = "failed"
            record["error"] = f"{type(e).__name__}: {e}"
            record["traceback"] = traceback.format_exc()[-4000:]
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    return f"{record['status'].upper():7s} {arch} {shape_name} {mesh_kind}"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (hillclimb variants)")
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    args = ap.parse_args()

    overrides: Dict[str, Any] = {}
    for kv in args.set:
        key, val = kv.split("=", 1)
        try:
            overrides[key] = int(val)
        except ValueError:
            overrides[key] = val == "true" if val in ("true", "false") else val

    out_dir = args.out or os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")
    )
    archs = [args.arch] if args.arch else list(CONFIGS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if not (args.all or args.arch or args.shape):
        ap.error("pass --all or --arch/--shape")

    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                t0 = time.time()
                msg = run_cell(
                    arch, shape, mesh_kind, out_dir,
                    force=args.force, overrides=overrides, tag=args.tag,
                )
                print(f"[{time.time()-t0:7.1f}s] {msg}", flush=True)


if __name__ == "__main__":
    main()
