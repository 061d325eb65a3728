import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch × shape × mesh) cell.

For each cell this driver:
1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod),
2. lowers the cell's step function (train / prefill / decode) with sharded
   ShapeDtypeStruct inputs — no allocation ever happens,
3. compiles, proving the sharding/collective configuration is coherent,
4. records ``memory_analysis()`` (bytes/device — proves HBM fit),
   ``cost_analysis()`` (FLOPs/bytes for §Roofline), and the collective
   traffic parsed from the compiled HLO,
5. writes a JSON record to ``benchmarks/results/dryrun/`` (cells are cached;
   re-runs skip completed cells unless --force).

Usage:
    python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--recipe baseline]
    python -m repro.launch.dryrun --all --both-meshes
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config, list_archs, supports_shape
from repro.distributed.sharding import RECIPES
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.models import build_decode_step, build_prefill_step, count_params
from repro.roofline.analysis import V5E, model_flops, roofline_terms
from repro.roofline.hlo import analyze
from repro.training.train_step import build_train_step

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


def cell_id(arch: str, shape: str, multi_pod: bool, recipe: str,
            overrides_tag: str = "") -> str:
    mesh_tag = "pod512" if multi_pod else "pod256"
    tag = f"__{overrides_tag}" if overrides_tag else ""
    return f"{arch}__{shape}__{mesh_tag}__{recipe}{tag}"


def _lower_cell(cfg, shape, mesh, recipe):
    from repro.distributed.ctx import sharding_ctx
    from repro.distributed.sharding import for_decode

    if shape.kind == "decode":
        recipe = for_decode(recipe)
    specs = input_specs(cfg, shape, mesh, recipe)
    if shape.kind == "train":
        step = build_train_step(cfg)
        args = (specs["state"], specs["batch"])
        donate = (0,)
    elif shape.kind == "prefill":
        step = build_prefill_step(cfg)
        args = (specs["params"], specs["batch"])
        donate = ()
    else:
        step = build_decode_step(cfg)
        args = (specs["params"], specs["cache"], specs["token"], specs["pos"])
        donate = (1,)  # cache is updated in place
    with mesh, sharding_ctx(mesh, recipe):
        lowered = jax.jit(step, donate_argnums=donate).lower(*args)
        compiled = lowered.compile()
    return lowered, compiled


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             recipe_name: str = "baseline", overrides: dict = None,
             overrides_tag: str = "", force: bool = False,
             results_dir: Path = RESULTS_DIR) -> dict:
    results_dir.mkdir(parents=True, exist_ok=True)
    cid = cell_id(arch, shape_name, multi_pod, recipe_name, overrides_tag)
    out_path = results_dir / f"{cid}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    ok, why = supports_shape(cfg, shape)
    record = {
        "cell": cid, "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": 512 if multi_pod else 256,
        "recipe": recipe_name, "overrides": overrides or {},
        "kind": shape.kind,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    if not ok:
        record.update({"status": "skipped", "reason": why})
        out_path.write_text(json.dumps(record, indent=2))
        return record

    recipe = RECIPES[recipe_name]
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        lowered, compiled = _lower_cell(cfg, shape, mesh, recipe)
    except Exception as e:  # a failing cell is a bug — record it loudly
        record.update({"status": "failed", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]})
        out_path.write_text(json.dumps(record, indent=2))
        return record
    compile_s = time.time() - t0

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    # loop-aware analyzer: XLA cost_analysis counts while bodies once, so
    # scans (layers × microbatches × attention blocks) would be undercounted
    la = analyze(compiled.as_text())
    coll = la["collectives"]

    flops = la["flops"]
    hbm_bytes = la["bytes"]
    terms = roofline_terms(flops, hbm_bytes, coll["total"], V5E)

    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    n_active = count_params(cfg, active_only=True, include_embed=False)
    mf = model_flops(n_active, tokens, "train" if shape.kind == "train" else "serve")
    chips = record["chips"]
    mf_per_dev = mf / chips

    record.update({
        "status": "ok",
        "compile_seconds": round(compile_s, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_live_bytes": ma.argument_size_in_bytes + ma.temp_size_in_bytes
                               + ma.output_size_in_bytes - ma.alias_size_in_bytes,
            "hbm_budget_bytes": int(V5E.hbm_bytes),
            "fits": (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                     + ma.output_size_in_bytes - ma.alias_size_in_bytes)
                    <= V5E.hbm_bytes,
            # the CPU PjRt client ignores donate_argnums (alias bytes = 0);
            # on the TPU target the donated state aliases its output, so the
            # realistic criterion discounts the output buffer
            "fits_with_donation": (ma.argument_size_in_bytes
                                   + ma.temp_size_in_bytes
                                   - ma.alias_size_in_bytes) <= V5E.hbm_bytes,
        },
        "cost": {
            "flops_per_device": flops,
            "hbm_bytes_per_device": hbm_bytes,
            "transcendentals": la["transcendentals"],
            # XLA's own numbers (while bodies counted once) for provenance
            "xla_flops_per_iter": float(ca.get("flops", 0.0)),
            "xla_bytes_per_iter": float(ca.get("bytes accessed", 0.0)),
        },
        "collectives": coll,
        "roofline": terms,
        "model_flops": {
            "n_active_params": n_active,
            "tokens": tokens,
            "model_flops_total": mf,
            "model_flops_per_device": mf_per_dev,
            "useful_ratio": (mf_per_dev / flops) if flops else 0.0,
        },
    })
    out_path.write_text(json.dumps(record, indent=2))
    return record


def _fmt(rec: dict) -> str:
    if rec["status"] == "skipped":
        return f"{rec['cell']:70s} SKIP ({rec['reason'][:60]})"
    if rec["status"] == "failed":
        return f"{rec['cell']:70s} FAIL {rec['error'][:90]}"
    r = rec["roofline"]
    m = rec["memory"]
    return (f"{rec['cell']:70s} ok c={r['compute_s']*1e3:9.2f}ms "
            f"m={r['memory_s']*1e3:9.2f}ms x={r['collective_s']*1e3:9.2f}ms "
            f"dom={r['dominant']:10s} frac={r['roofline_fraction']:.3f} "
            f"live={m['peak_live_bytes']/1e9:6.2f}GB fit={m['fits']} "
            f"compile={rec['compile_seconds']:.0f}s")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--recipe", default="baseline")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    if not (args.all or args.arch):
        ap.error("pass --all or --arch")

    n_ok = n_skip = n_fail = 0
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                rec = run_cell(arch, shape, multi_pod=mp,
                               recipe_name=args.recipe, force=args.force)
                print(_fmt(rec), flush=True)
                n_ok += rec["status"] == "ok"
                n_skip += rec["status"] == "skipped"
                n_fail += rec["status"] == "failed"
    print(f"\ndone: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
