"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh)
cell with production shardings, record memory/cost/collective analysis.

MUST set the placeholder device count before ANY other import (jax locks
the device count on first init).
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse          # noqa: E402
import contextlib        # noqa: E402
import json              # noqa: E402
import pathlib           # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402

from repro.configs.base import ARCH_IDS, SHAPES, shape_cells   # noqa: E402
from repro.launch.mesh import make_production_mesh             # noqa: E402
from repro.models.sharding import axis_size, rules_override    # noqa: E402

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8}

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_LINE_RE = re.compile(
    r"=\s+(.+?)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")


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


def collective_bytes(hlo: str) -> dict:
    """Per-device bytes moved by collectives, parsed from partitioned HLO.

    Weights (ring algorithms): all-reduce 2x output size; others 1x.
    ``-done`` ops are skipped (their ``-start`` was already counted).
    """
    out = {op: 0 for op in _COLL_OPS}
    counts = {op: 0 for op in _COLL_OPS}
    for line in hlo.splitlines():
        if "-done(" in line:
            continue
        m = _LINE_RE.search(line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        out[op] += b * (2 if op == "all-reduce" else 1)
        counts[op] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


def _lower_compile(fn, args, in_sh, out_sh, donate):
    kw = {}
    if in_sh is not None:
        kw["in_shardings"] = in_sh
    if out_sh is not None:
        kw["out_shardings"] = out_sh
    if donate:
        kw["donate_argnums"] = donate
    jitted = jax.jit(fn, **kw)
    lowered = jitted.lower(*args)
    compiled = lowered.compile()
    return lowered, compiled


def analyze(compiled) -> dict:
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):   # pre-0.5 returns [dict]
        ca = ca[0] if ca else {}
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "collectives": collective_bytes(hlo),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "generated_code_bytes": ma.generated_code_size_in_bytes,
        },
    }


def run_cell(arch: str, shape: str, multi_pod: bool, force=False) -> dict:
    mesh_name = "pod512" if multi_pod else "pod256"
    out_path = RESULTS / mesh_name / f"{arch}__{shape}.json"
    out_path.parent.mkdir(parents=True, exist_ok=True)
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    from repro.launch import steps as Steps
    Steps.run_plan_rules = Steps.plan_rules
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    record = {"arch": arch, "shape": shape, "mesh": mesh_name,
              "n_chips": int(mesh.devices.size), "ok": False}
    try:
        with jax.set_mesh(mesh):
            rules = Steps.run_plan_rules(arch, shape)
            record["rules"] = {k: list(v) for k, v in rules.items()}
            with rules_override(**rules):
                if arch.startswith("svfusion"):
                    bundle = Steps.build_svfusion_bundle(shape, mesh)
                else:
                    bundle = Steps.build_bundle(arch, shape)
                lowered, compiled = _lower_compile(
                    bundle.fn, bundle.abstract_args, bundle.in_shardings,
                    bundle.out_shardings, bundle.donate_argnums)
                record.update(analyze(compiled))
                record["model_flops"] = bundle.model_flops
                record["notes"] = bundle.notes
                record["kind"] = bundle.kind
                units = []
                for u in bundle.cost_units:
                    _, uc = _lower_compile(u.fn, u.abstract_args,
                                           u.in_shardings, None, ())
                    ua = analyze(uc)
                    ua["name"], ua["multiplier"] = u.name, u.multiplier
                    units.append(ua)
                record["units"] = units
                # scan-corrected totals (DESIGN.md §8)
                record["flops_corrected"] = record["flops"] + sum(
                    u["flops"] * u["multiplier"] for u in units)
                record["bytes_corrected"] = record["bytes_accessed"] + sum(
                    u["bytes_accessed"] * u["multiplier"] for u in units)
                record["coll_corrected"] = (
                    record["collectives"]["total_bytes"] + sum(
                        u["collectives"]["total_bytes"] * u["multiplier"]
                        for u in units))
        record["ok"] = True
    except Exception as e:
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    record["elapsed_s"] = round(time.time() - t0, 2)
    out_path.write_text(json.dumps(record, indent=1))
    status = "OK " if record["ok"] else "FAIL"
    print(f"[{status}] {mesh_name} {arch:20s} {shape:12s} "
          f"{record['elapsed_s']:7.1f}s "
          f"{record.get('error', '')[:90]}", flush=True)
    return record


def all_cells():
    cells = []
    for arch in ARCH_IDS:
        for shape in shape_cells(arch):
            cells.append((arch, shape))
    cells.append(("svfusion_deep1b", "search_10k"))
    cells.append(("svfusion_msturing", "search_1k"))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod256", "pod512", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    meshes = {"pod256": [False], "pod512": [True],
              "both": [False, True]}[args.mesh]
    if args.all:
        cells = all_cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    n_ok = n_fail = 0
    for multi in meshes:
        for arch, shape in cells:
            rec = run_cell(arch, shape, multi, force=args.force)
            n_ok += rec["ok"]
            n_fail += not rec["ok"]
    print(f"dry-run: {n_ok} ok, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
