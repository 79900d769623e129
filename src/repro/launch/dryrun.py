"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Usage (resumable; JSON per cell under experiments/dryrun/):
    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b \
        --shape train_4k --mesh single

The FIRST two lines below must run before any other import so the 512
placeholder host devices exist when jax initializes. Do not move them.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import base as cfg_base  # noqa: E402
from repro.configs import shapes as shp  # noqa: E402
from repro.launch import hlo_cost  # noqa: E402
from repro.launch import mesh as mesh_lib  # noqa: E402
from repro.launch import sharding, specs  # noqa: E402
from repro.models.lm import LM  # noqa: E402
from repro.training import optimizer, train_step as ts_lib  # noqa: E402

# The chip the production meshes model (16 GB of HBM per device); records
# carry it so benchmarks/roofline.py reads that chip's peaks.
TARGET_DEVICE_KIND = "TPU v5 lite"

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

# Per-arch train-cell knobs (microbatches, accumulation dtype) chosen so the
# per-device footprint fits a 16 GB HBM chip — derivations in EXPERIMENTS.md.
TRAIN_KNOBS = {
    "qwen2.5-14b": dict(microbatches=8, accum_dtype="float32"),
    "llava-next-34b": dict(microbatches=16, accum_dtype="bfloat16"),
    "moonshot-v1-16b-a3b": dict(microbatches=8, accum_dtype="bfloat16"),
    "qwen2-moe-a2.7b": dict(microbatches=8, accum_dtype="bfloat16"),
    # SSD intra-chunk decay tensors (b, c, l, l, h) scale with the
    # per-device microbatch — mb=8 keeps them ~2.7 GB under remat.
    "mamba2-2.7b": dict(microbatches=8, accum_dtype="float32"),
    "zamba2-1.2b": dict(microbatches=8, accum_dtype="float32"),
}
DEFAULT_TRAIN_KNOBS = dict(microbatches=4, accum_dtype="float32")

# HLO collective parsing lives in launch/hlo_cost (one parser for the
# dry-run census, the roofline, and the analysis gate); re-exported here
# for existing callers. The private copy this file used to carry had
# drifted (no f8 variants, no s4/u4).
parse_collectives = hlo_cost.parse_collectives


# ---------------------------------------------------------------------------
# Cell construction
# ---------------------------------------------------------------------------


def build_model(cfg, shape: shp.ShapeSpec, variant: dict | None = None
                ) -> LM:
    """``variant`` (perf-iteration knobs, see benchmarks/hillclimb.py):
    shard_acts (mesh_axes constraints), q_chunk, attn_impl, moe_dispatch."""
    v = variant or {}
    remat = v.get("remat") or ("full" if shape.step == "train" else "none")
    mesh_axes = ()
    if v.get("shard_acts"):
        mesh_axes = (("pod", "data", "model") if v.get("multi_pod")
                     else ("data", "model"))
    moe_groups = v.get("moe_groups", 1)
    if moe_groups == "dp":
        moe_groups = 32 if v.get("multi_pod") else 16
    return LM(cfg,
              attn_impl=v.get("attn_impl", "auto"),
              q_chunk=v.get("q_chunk", 2048), kv_chunk=v.get("q_chunk",
                                                             2048),
              ssd_chunk=256, vocab_chunk=256, remat=remat,
              mesh_axes=mesh_axes,
              moe_dispatch=v.get("moe_dispatch", "sort"),
              moe_groups=moe_groups)


def lower_cell(arch: str, shape: shp.ShapeSpec, mesh,
               variant: dict | None = None):
    """Build + lower one cell. Returns (lowered, meta)."""
    cfg = cfg_base.get(arch)
    if variant is not None:
        variant = dict(variant)
        variant["multi_pod"] = "pod" in mesh.axis_names
    model = build_model(cfg, shape, variant)
    meta = {
        "arch": arch, "shape": shape.name, "step": shape.step,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "n_params": cfg.n_params(), "n_active_params": cfg.n_active_params(),
        "family": cfg.family,
    }

    if shape.step == "train":
        knobs = TRAIN_KNOBS.get(arch, DEFAULT_TRAIN_KNOBS)
        tcfg = ts_lib.TrainConfig(
            microbatches=knobs["microbatches"],
            accum_dtype=knobs["accum_dtype"],
        )
        meta.update(knobs)
        step_fn = ts_lib.make_train_step(model, tcfg)
        state_shapes = specs.train_state_shapes(model)
        batch_shapes = specs.batch_specs(
            cfg, shape.seq_len, shape.global_batch, with_labels=True
        )
        state_sh = sharding.to_named(
            ts_lib.TrainState(
                params=sharding.param_specs(state_shapes.params, mesh),
                opt=sharding.opt_specs(state_shapes.params, mesh),
                ledger_head=jax.sharding.PartitionSpec(),
            ), mesh,
        )
        batch_sh = sharding.to_named(
            sharding.batch_pspecs(batch_shapes, mesh), mesh
        )
        fn = jax.jit(
            step_fn,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
        lowered = fn.lower(state_shapes, batch_shapes)
    elif shape.step == "prefill":
        batch_shapes = specs.batch_specs(
            cfg, shape.seq_len, shape.global_batch, with_labels=False
        )
        cache_shapes = specs.cache_shapes(
            model, shape.global_batch, shape.seq_len
        )
        p_shapes = specs.param_shapes(model)
        p_sh = sharding.to_named(sharding.param_specs(p_shapes, mesh), mesh)
        b_sh = sharding.to_named(
            sharding.batch_pspecs(batch_shapes, mesh), mesh
        )
        c_sh = sharding.to_named(
            sharding.cache_pspecs(cache_shapes, mesh), mesh
        )
        fn = jax.jit(
            model.prefill,
            in_shardings=(p_sh, b_sh, c_sh),
            out_shardings=(None, c_sh),
            donate_argnums=(2,),
        )
        lowered = fn.lower(p_shapes, batch_shapes, cache_shapes)
    elif shape.step == "decode":
        cache_shapes = specs.cache_shapes(
            model, shape.global_batch, shape.seq_len
        )
        p_shapes = specs.param_shapes(model)
        tok_spec, pos_spec = specs.decode_token_specs(shape.global_batch)
        p_sh = sharding.to_named(sharding.param_specs(p_shapes, mesh), mesh)
        c_sh = sharding.to_named(
            sharding.cache_pspecs(cache_shapes, mesh), mesh
        )
        t_sh = sharding.to_named(
            sharding.token_pspec(shape.global_batch, mesh), mesh
        )
        fn = jax.jit(
            model.decode_step,
            in_shardings=(p_sh, c_sh, t_sh,
                          sharding.to_named(jax.sharding.PartitionSpec(),
                                            mesh)),
            out_shardings=(None, c_sh),
            donate_argnums=(1,),
        )
        lowered = fn.lower(p_shapes, cache_shapes, tok_spec, pos_spec)
    else:
        raise ValueError(shape.step)
    return lowered, meta


# The combined beyond-paper optimization bundle (§Perf): explicit
# activation sharding + sort-free per-DP-group MoE dispatch.
OPTIMIZED_VARIANT = {"shard_acts": True, "moe_dispatch": "cumsum",
                     "moe_groups": "dp"}


def run_cell(arch: str, shape: shp.ShapeSpec, mesh_name: str,
             out_dir: str, *, force: bool = False,
             variant: dict | None = None) -> dict:
    path = os.path.join(
        out_dir, f"{arch}__{shape.name}__{mesh_name}.json"
    )
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    cfg = cfg_base.get(arch)
    ok, reason = shp.applicable(cfg, shape)
    if not ok:
        rec = {"arch": arch, "shape": shape.name, "mesh": mesh_name,
               "status": "skipped", "reason": reason}
        _write(path, rec)
        return rec

    mesh = mesh_lib.make_production_mesh(multi_pod=(mesh_name == "multi"))
    t0 = time.time()
    try:
        with mesh:
            lowered, meta = lower_cell(arch, shape, mesh, variant=variant)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo_text = compiled.as_text()
            coll = parse_collectives(hlo_text)
            tc_cost = hlo_cost.analyze(hlo_text)  # trip-count-corrected
            _save_hlo(path, hlo_text)
        rec = {
            **meta,
            "mesh": mesh_name,
            "n_devices": mesh.size,
            "device_kind": TARGET_DEVICE_KIND,
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "generated_code_bytes": getattr(
                    mem, "generated_code_size_in_bytes", 0
                ),
                "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
            },
            "cost": {
                "flops": cost.get("flops", 0.0),
                "bytes_accessed": cost.get("bytes accessed", 0.0),
                "transcendentals": cost.get("transcendentals", 0.0),
            },
            # Trip-count-corrected costs (launch/hlo_cost.py) — XLA's own
            # cost_analysis counts while bodies once; these multiply loops
            # out and are what §Roofline consumes.
            "hlo_cost": tc_cost,
            "collectives": coll,
        }
    except Exception as e:  # record the failure; the suite flags it
        rec = {
            "arch": arch, "shape": shape.name, "mesh": mesh_name,
            "status": "error", "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
    _write(path, rec)
    return rec


def _save_hlo(json_path: str, hlo_text: str) -> None:
    import gzip

    os.makedirs(os.path.dirname(json_path), exist_ok=True)
    with gzip.open(json_path.replace(".json", ".hlo.gz"), "wt") as f:
        f.write(hlo_text)


def _write(path: str, rec: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def run_fabric_cell(variant: str, mesh_name: str, out_dir: str,
                    *, b_loc: int = 100, force: bool = False) -> dict:
    """Dry-run the paper's own workload: the sharded fabric step.

    ``variant``: "fastfabric" (O-I+O-II+vectorized commit), "fabric-v12"
    (full-payload consensus, serial admission + commit),
    "fastfabric-sharded" (world state bucket-partitioned over the `model`
    axis — launch/state_sharding), or "fastfabric-pipelined" (sharded
    state + the depth-8 device-side block pipeline of repro/pipeline: one
    consensus gather and one routed MVCC gather per 8-block window).
    PAPER_DIMS = 2.9 KB transactions, one channel per data rank, one
    orderer-replica / validation worker per model rank, 100
    txs/worker/round (per block for the pipelined variant).
    """
    from repro.core import types as ftypes  # noqa: PLC0415
    from repro.launch import fabric_step as fs  # noqa: PLC0415

    path = os.path.join(out_dir, f"{variant}__step__{mesh_name}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    mesh = mesh_lib.make_production_mesh(multi_pod=(mesh_name == "multi"))
    dims = ftypes.PAPER_DIMS
    cfg = {
        "fastfabric": fs.FASTFABRIC_STEP,
        "fabric-v12": fs.FABRIC_V12_STEP,
        "fastfabric-sharded": fs.FASTFABRIC_SHARDED_STEP,
        "fastfabric-pipelined": fs.FASTFABRIC_PIPELINED_STEP,
    }[variant]
    t0 = time.time()
    try:
        with mesh:
            step = fs.make_fabric_step(dims, cfg, mesh)
            n_ch = mesh.shape["data"] * mesh.shape.get("pod", 1)
            state_shape = jax.eval_shape(
                lambda: fs.create_mesh_state(n_ch, dims)
            )
            wire_s, ids_s = fs.input_specs(
                mesh, dims, b_loc=b_loc,
                pipeline_depth=cfg.pipeline_depth,
            )
            fn = jax.jit(step, donate_argnums=(0,))
            lowered = fn.lower(state_shape, wire_s, ids_s)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo_text = compiled.as_text()
            coll = parse_collectives(hlo_text)
            tc_cost = hlo_cost.analyze(hlo_text)
            _save_hlo(path, hlo_text)
        txs = n_ch * b_loc * mesh.shape["model"] * cfg.pipeline_depth
        rec = {
            "arch": variant, "shape": "step", "step": "fabric",
            "mesh": mesh_name, "n_devices": mesh.size, "status": "ok",
            "device_kind": TARGET_DEVICE_KIND,
            "txs_per_round": txs, "payload_bytes": dims.payload_bytes,
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "generated_code_bytes": getattr(
                    mem, "generated_code_size_in_bytes", 0),
                "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
            },
            "cost": {
                "flops": cost.get("flops", 0.0),
                "bytes_accessed": cost.get("bytes accessed", 0.0),
                "transcendentals": cost.get("transcendentals", 0.0),
            },
            "hlo_cost": tc_cost,
            "collectives": coll,
        }
    except Exception as e:
        rec = {"arch": variant, "shape": "step", "mesh": mesh_name,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    _write(path, rec)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--fabric", action="store_true",
                    help="also dry-run the paper's fabric step cells")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the beyond-paper optimization bundle and "
                         "write to experiments/optimized/")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.out is None:
        args.out = os.path.abspath(
            OUT_DIR.replace("dryrun", "optimized") if args.optimized
            else OUT_DIR
        )
    variant = OPTIMIZED_VARIANT if args.optimized else None

    fabric_variants = ("fastfabric", "fabric-v12", "fastfabric-sharded",
                       "fastfabric-pipelined")
    if args.fabric or (args.arch in fabric_variants):
        variants = ([args.arch] if args.arch in fabric_variants
                    else list(fabric_variants))
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        for v in variants:
            for m in meshes:
                rec = run_fabric_cell(v, m, args.out, force=args.force)
                if rec["status"] == "ok":
                    print(f"[ok]   {v:22s} step         {m:6s}"
                          f" compile={rec['compile_s']:7.1f}s"
                          f" coll={rec['collectives']['total_wire_bytes']:.3e}B")
                else:
                    print(f"[ERR]  {v}: {rec['error']}")
        if not args.all:
            return

    archs = [args.arch] if args.arch else list(cfg_base.ARCH_IDS)
    shapes = ([shp.SHAPES_BY_NAME[args.shape]] if args.shape
              else list(shp.SHAPES))
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if not (args.all or args.arch or args.shape):
        ap.error("pass --all or --arch/--shape")

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                rec = run_cell(arch, shape, mesh_name, args.out,
                               force=args.force, variant=variant)
                status = rec["status"]
                if status == "ok":
                    n_ok += 1
                    mem = rec["memory"]
                    per_dev = (mem["argument_bytes"] + mem["temp_bytes"]
                               + mem["output_bytes"])
                    print(
                        f"[ok]   {arch:22s} {shape.name:12s} {mesh_name:6s}"
                        f" compile={rec['compile_s']:7.1f}s"
                        f" flops={rec['cost']['flops']:.3e}"
                        f" coll={rec['collectives']['total_wire_bytes']:.3e}B"
                    )
                elif status == "skipped":
                    n_skip += 1
                    print(f"[skip] {arch:22s} {shape.name:12s} {mesh_name}")
                else:
                    n_err += 1
                    print(f"[ERR]  {arch:22s} {shape.name:12s} {mesh_name}: "
                          f"{rec['error']}")
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
