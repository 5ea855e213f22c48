import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=512")
# The two lines above MUST run before any other import (jax locks the device
# count on first initialisation).  Everything below may import jax.

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory / cost / collective statistics.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-110b \
        --shape train_4k --mesh multi
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>[__tag].json and
feed benchmarks/roofline.py and EXPERIMENTS.md §Dry-run/§Roofline.
"""

import argparse
import dataclasses
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax

from repro.configs.registry import ARCHS, get_config
from repro.launch import steps as ST
from repro.launch.mesh import make_production_mesh
from repro.models.config import SHAPES
from repro.models.model import RunFlags

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(m) -> int:
    dt, dims = m.group(1), m.group(2)
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def parse_collectives(hlo_text: str):
    """Per-device collective operand bytes by op kind, from optimized HLO.

    Operand shapes appear inline in the op's argument list; we sum operand
    sizes (start/done pairs are counted once via the -start form; plain
    forms counted directly)."""
    operand = {k: 0 for k in _COLLECTIVES}
    wire = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # result type may be a tuple — variadic all-reduces are common:
        #   %ar = (f32[1000,64]{1,0}, f32[1000]{0}) all-reduce(%a, %b), ...
        m = re.search(r"=\s+(.+?)\s+(" +
                      "|".join(_COLLECTIVES) + r")(-start|-done)?\(", s)
        if not m:
            continue
        if m.group(3) == "-done":
            continue        # counted at the -start form
        kind = m.group(2)
        grp = re.search(r"replica_groups=\[(\d+),(\d+)\]", s)
        gsize = int(grp.group(2)) if grp else 0
        if not gsize:
            grp2 = re.search(r"replica_groups=\{\{([\d,]+)\}", s)
            gsize = len(grp2.group(1).split(",")) if grp2 else 2
        # result shape(s) sit between '=' and the op name
        res = sum(_shape_bytes(sm) for sm in _SHAPE_RE.finditer(m.group(1)))
        g = max(gsize, 1)
        ring = (g - 1) / g
        # per-device operand bytes (spec proxy) and ring wire-traffic bytes
        if kind == "all-gather":
            op_b, wire_b = res // g, res * ring
        elif kind == "all-reduce":
            op_b, wire_b = res, 2 * res * ring
        elif kind == "reduce-scatter":
            op_b, wire_b = res * g, res * g * ring
        elif kind == "all-to-all":
            op_b, wire_b = res, res * ring
        else:  # collective-permute: one hop
            op_b, wire_b = res, res
        operand[kind] += op_b
        wire[kind] += wire_b
        counts[kind] += 1
    return operand, wire, counts


_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\]")
_DOT_RE = re.compile(
    r"=\s*\w+\[([\d,]*)\][^ ]*\s+dot\(\s*%([\w.\-]+)",)
_LHS_C_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def parse_dot_flops(hlo_text: str) -> float:
    """Sum 2 * prod(result_shape) * prod(contracted lhs dims) over every
    `dot` op, INCLUDING dots inside fusion computations.

    Needed because XLA:CPU's HloCostAnalysis does not attribute the flops
    of a dot that was wrapped into a fusion computation (verified: a
    (8.4M x 64) @ (64 x 1000) dot fused with its elementwise consumers
    reports ~0 of its 1.07e15 flops).  While bodies still count once —
    handled by the same unrolled calibration as the rest."""
    shapes = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            name, _, dims = m.groups()
            shapes[name] = [int(d) for d in dims.split(",")] if dims else []
    total = 0.0
    for line in hlo_text.splitlines():
        if " dot(" not in line:
            continue
        m = _DOT_RE.search(line)
        if not m:
            continue
        res_dims, lhs_name = m.groups()
        res = 1
        for d in (res_dims.split(",") if res_dims else []):
            res *= int(d)
        lhs = shapes.get(lhs_name)
        mc = _LHS_C_RE.search(line)
        contract = 1
        if lhs is not None and mc and mc.group(1):
            for i in mc.group(1).split(","):
                contract *= lhs[int(i)]
        total += 2.0 * res * contract
    return total


def memory_dict(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    keys = ("generated_code_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "temp_size_in_bytes",
            "alias_size_in_bytes", "host_generated_code_size_in_bytes",
            "host_argument_size_in_bytes", "host_output_size_in_bytes",
            "host_temp_size_in_bytes", "peak_memory_in_bytes")
    out = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def n_units(cfg) -> int:
    """Number of outer scanned units (layers, or groups for hybrid/vlm)."""
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.shared_attn_every
    if cfg.family == "vlm":
        return cfg.n_layers // cfg.cross_attn_every
    return cfg.n_layers


def with_units(cfg, n: int):
    if cfg.family == "hybrid":
        return dataclasses.replace(cfg, n_layers=n * cfg.shared_attn_every)
    if cfg.family == "vlm":
        return dataclasses.replace(cfg, n_layers=n * cfg.cross_attn_every)
    return dataclasses.replace(cfg, n_layers=n)


_CAL_METRICS = ("flops", "bytes", "dot_flops")


def _collect_costs(compiled):
    ca = compiled.cost_analysis()
    hlo = compiled.as_text()
    _, wire, _ = parse_collectives(hlo)
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "dot_flops": parse_dot_flops(hlo),
            "wire": wire}


def calibrate_cell(arch, shape_name, mesh, flags: RunFlags, cfg):
    """XLA counts while/scan bodies ONCE in cost_analysis (verified; see
    EXPERIMENTS.md §Dry-run methodology).  To recover true per-step costs we
    compile two small fully-unrolled variants (1 and 2 outer layer units,
    attention/block loops unrolled, identical widths and block sizes) and
    scale:  total(L) = base + L * per_unit."""
    calib_flags = dataclasses.replace(flags, scan_layers=False,
                                      attn_unroll=True)
    costs = {}
    for n in (1, 2):
        cfg_n = with_units(cfg, n)
        bundle = ST.build(arch, shape_name, mesh, flags=calib_flags,
                          cfg=cfg_n)
        t0 = time.perf_counter()
        compiled = bundle.lower().compile()
        costs[n] = _collect_costs(compiled)
        costs[n]["compile_s"] = round(time.perf_counter() - t0, 2)

    units = n_units(cfg)
    out = {"calib_units": units,
           "calib_compile_s": [costs[1]["compile_s"], costs[2]["compile_s"]]}
    for m in _CAL_METRICS:
        per = costs[2][m] - costs[1][m]
        base = costs[1][m] - per
        out[f"{m}_per_unit"] = per
        out[f"{m}_base"] = base
        out[f"{m}_corrected"] = base + units * per
    wire_tot = {}
    for k in costs[1]["wire"]:
        per = costs[2]["wire"][k] - costs[1]["wire"][k]
        base = costs[1]["wire"][k] - per
        wire_tot[k] = max(base + units * per, 0.0)
    out["wire_corrected"] = wire_tot
    out["wire_corrected_total"] = float(sum(wire_tot.values()))
    return out


def model_flops(cfg, shape) -> float:
    n_active = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch      # decode: 1 tok/seq


def cell_supported(arch: str, shape_name: str):
    cfg = get_config(arch)
    if shape_name == "long_500k" and not cfg.is_subquadratic:
        return False, ("full-attention architecture: 500k dense attention "
                       "is out of scope by assignment (DESIGN.md §Shapes)")
    return True, ""


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             flags: RunFlags, tag: str = "", save: bool = True,
             calibrate: bool = True) -> dict:
    shape = SHAPES[shape_name]
    multi = mesh_kind == "multi"
    n_dev = 512 if multi else 256
    mesh = make_production_mesh(multi_pod=multi)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "devices": n_dev, "flags": dataclasses.asdict(flags),
           "tag": tag, "ok": False, "parser_v2": True}
    t0 = time.perf_counter()
    try:
        bundle = ST.build(arch, shape_name, mesh, flags=flags)
        lowered = bundle.lower()
        rec["time_lower_s"] = round(time.perf_counter() - t0, 2)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["time_compile_s"] = round(time.perf_counter() - t1, 2)

        ca = compiled.cost_analysis()
        rec["hlo_flops_per_device"] = float(ca.get("flops", 0.0))
        rec["hlo_bytes_per_device"] = float(ca.get("bytes accessed", 0.0))
        rec["memory"] = memory_dict(compiled)

        hlo = compiled.as_text()
        rec["hlo_dot_flops_per_device"] = parse_dot_flops(hlo)
        operand, wire, counts = parse_collectives(hlo)
        rec["collective_operand_bytes_per_device"] = operand
        rec["collective_wire_bytes_per_device"] = wire
        rec["collective_counts"] = counts
        rec["collective_total_per_device"] = float(sum(wire.values()))

        cfg = bundle.cfg
        rec["n_params"] = cfg.n_params()
        rec["n_active_params"] = cfg.n_active_params()
        rec["model_flops"] = model_flops(cfg, shape)
        if calibrate:
            rec["calib"] = calibrate_cell(arch, shape_name, mesh, flags, cfg)
        rec["ok"] = True
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["time_total_s"] = round(time.perf_counter() - t0, 2)
    if save:
        ARTIFACTS.mkdir(parents=True, exist_ok=True)
        sfx = f"__{tag}" if tag else ""
        path = ARTIFACTS / f"{arch}__{shape_name}__{mesh_kind}{sfx}.json"
        path.write_text(json.dumps(rec, indent=1))
        rec["artifact"] = str(path)
    return rec


def flags_from_args(args, shape_name: str = "") -> RunFlags:
    block_q, block_kv = args.block_q, args.block_kv
    if shape_name == "prefill_32k" and (block_q, block_kv) == (512, 1024):
        # default blocking for the 32k prompt: bigger tiles, fewer blocks
        block_q = block_kv = 2048
    return RunFlags(remat=args.remat, block_q=block_q,
                    block_kv=block_kv, skip_blocks=args.skip_blocks,
                    loss_chunk=args.loss_chunk, fold_heads=args.fold_heads,
                    cache_seq_model=args.cache_seq_model,
                    seq_shard_acts=args.seq_shard_acts)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--remat", default="full",
                    choices=["none", "dots", "full"])
    ap.add_argument("--block-q", type=int, default=512, dest="block_q")
    ap.add_argument("--block-kv", type=int, default=1024, dest="block_kv")
    ap.add_argument("--skip-blocks", action="store_true", dest="skip_blocks")
    ap.add_argument("--loss-chunk", type=int, default=0, dest="loss_chunk")
    ap.add_argument("--fold-heads", action="store_true", dest="fold_heads")
    ap.add_argument("--cache-seq-model", action="store_true",
                    dest="cache_seq_model")
    ap.add_argument("--seq-shard-acts", action="store_true",
                    dest="seq_shard_acts")
    args = ap.parse_args()

    cells = []
    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for a in archs:
        for s in shapes:
            ok, why = cell_supported(a, s)
            for m in meshes:
                cells.append((a, s, m, ok, why))

    if args.list:
        for a, s, m, ok, why in cells:
            print(f"{a:22s} {s:12s} {m:7s} {'RUN' if ok else 'SKIP: ' + why}")
        return

    failures = 0
    for a, s, m, ok, why in cells:
        flags = flags_from_args(args, s)
        if not ok:
            print(f"[skip] {a} {s} {m}: {why}", flush=True)
            if not args.tag:
                ARTIFACTS.mkdir(parents=True, exist_ok=True)
                (ARTIFACTS / f"{a}__{s}__{m}.json").write_text(json.dumps(
                    {"arch": a, "shape": s, "mesh": m, "ok": True,
                     "skipped": True, "skip_reason": why}, indent=1))
            continue
        rec = run_cell(a, s, m, flags, tag=args.tag)
        if rec["ok"]:
            mem = rec.get("memory", {})
            print(f"[ok]   {a} {s} {m}: lower {rec['time_lower_s']}s "
                  f"compile {rec['time_compile_s']}s "
                  f"flops/dev {rec['hlo_flops_per_device']:.3e} "
                  f"coll/dev {rec['collective_total_per_device']:.3e}B "
                  f"args/dev {mem.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
                  f"temp/dev {mem.get('temp_size_in_bytes', 0)/2**30:.2f}GiB",
                  flush=True)
        else:
            failures += 1
            print(f"[FAIL] {a} {s} {m}: {rec['error']}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
