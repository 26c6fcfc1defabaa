import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import: jax locks the device count on first init.
# Never set this globally — smoke tests and benches must see 1 device.
# CPU only, children included: a dry run must never take a chip.

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and extract memory / cost / collective analyses.

    python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all            # sweep, subprocess per cell

Per cell we record (experiments/dryrun/<arch>__<shape>__<mesh>.json):
  · compile success, wall times
  · memory_analysis(): per-device argument/output/temp bytes (fits < 16 GB?)
  · cost_analysis() flops (per-iteration; loop-corrected totals come from
    the HLO analyzer) + loop-aware dot-FLOPs and collective wire bytes
  · collective op counts by kind (the collective schedule)
EXPERIMENTS.md §Dry-run / §Roofline are generated from these JSONs.
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, all_configs, cell_supported, get_config
from repro.configs.base import ModelConfig, ShapeSpec
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import HBM_BYTES, make_production_mesh
from repro.serve.kv_cache import cache_defs
from repro.sharding import params as prm
from repro.sharding.axes import DEFAULT_RULES, ShardCtx
from repro.train.optimizer import OptConfig
from repro.train.step import abstract_state, make_train_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "../../../experiments/dryrun")

# ≥40 B-param models shard optimizer state over the pod axis too (ZeRO over
# DCI) — without it a 16 GB v5e cannot hold its slice of a 236 B model. The
# cost shows up as pod-crossing all-gathers in the §Roofline collective term.
BIG_MODELS = {"deepseek-v2-236b", "phi3.5-moe-42b-a6.6b", "jamba-v0.1-52b"}

# grad-accumulation microbatches per train cell (activation-memory control;
# chosen so peak_bytes_per_device < 16 GB with headroom)
MICROBATCHES = {"deepseek-v2-236b": 16, "phi3.5-moe-42b-a6.6b": 4,
                "jamba-v0.1-52b": 8, "internvl2-26b": 4, "nemotron-4-15b": 2,
                "mistral-nemo-12b": 2, "whisper-large-v3": 2}


# §Perf iteration 2 (see EXPERIMENTS.md): parameter-sharding stage per cell.
#   - inference (prefill/decode): params shard over `model` only — FSDP
#     gathers per decoded token were measured at ~12 GB/step on phi-42B.
#   - train ≤52 B params: ZeRO-2 — params replicated over `data`, only
#     moments/grads sharded; kills the per-microbatch weight all-gathers.
#   - train 236 B (deepseek): ZeRO-3 stays (params don't fit replicated).
ZERO3_MODELS = {"deepseek-v2-236b"}


def make_ctx(cfg: ModelConfig, multi_pod: bool,
             kind: str = "train") -> ShardCtx:
    # NOTES from the §Perf log (EXPERIMENTS.md):
    #  · ZeRO-over-pod (embed → ("pod","data")) triggers XLA SPMD
    #    "involuntary full rematerialization" (replicated dots, 6.6× flops)
    #    — int8 moments + microbatching is the memory lever instead.
    #  · ZeRO-2 for train was measured WORSE than ZeRO-3 once the shard_map
    #    MLP landed (activation gathers dominate; params-replicated memory
    #    costs 2-9 GiB/dev for nothing) — train keeps ZeRO-3.
    #  · inference replicates params over `data` (TP over `model` only):
    #    FSDP gathers were ~12 GB per decoded token on phi-42B.
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = dict(DEFAULT_RULES)
    if kind != "train" and cfg.name not in ZERO3_MODELS:
        # inference: TP over `model` only — except 236 B-class models whose
        # bf16 params (29.5 GB per model-shard) cannot replicate over data
        rules["embed"] = ()
    return ShardCtx(mesh=mesh, rules=rules)


def moment_ctx(ctx: ShardCtx) -> ShardCtx:
    """Optimizer moments always shard over data (ZeRO-2's sharded state)."""
    return ShardCtx(mesh=ctx.mesh, rules=dict(DEFAULT_RULES))


def sds(ctx: ShardCtx, shape, dtype, axes):
    return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                sharding=ctx.sharding(axes, shape))


def input_specs(cfg: ModelConfig, shape: ShapeSpec, ctx: ShardCtx) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        if cfg.enc_dec:
            Td = cfg.max_decoder_len
            return {
                "frames": sds(ctx, (B, S, cfg.d_model), jnp.float32,
                              ("batch", "seq", None)),
                "tokens": sds(ctx, (B, Td), jnp.int32, ("batch", None)),
                "targets": sds(ctx, (B, Td), jnp.int32, ("batch", None)),
                "mask": sds(ctx, (B, Td), jnp.float32, ("batch", None)),
            }
        out = {
            "tokens": sds(ctx, (B, S), jnp.int32, ("batch", "seq")),
            "targets": sds(ctx, (B, S), jnp.int32, ("batch", "seq")),
            "mask": sds(ctx, (B, S), jnp.float32, ("batch", "seq")),
        }
        if cfg.frontend != "none":
            out["frontend_embed"] = sds(
                ctx, (B, cfg.frontend_tokens, cfg.frontend_dim), jnp.float32,
                ("batch", None, None))
        return out
    if shape.kind == "prefill":
        if cfg.enc_dec:
            return {"frames": sds(ctx, (B, S, cfg.d_model), jnp.float32,
                                  ("batch", "seq", None))}
        out = {"tokens": sds(ctx, (B, S), jnp.int32, ("batch", "seq"))}
        if cfg.frontend != "none":
            out["frontend_embed"] = sds(
                ctx, (B, cfg.frontend_tokens, cfg.frontend_dim), jnp.float32,
                ("batch", None, None))
        return out
    # decode: one new token against a seq_len cache
    msize = ctx.axis_size("model")
    cdefs = cache_defs(cfg, B, S, msize)
    return {
        "cache": prm.abstract(cdefs, ctx),
        "tokens": sds(ctx, (B,), jnp.int32, ("batch",)),
        "pos": sds(ctx, (B,), jnp.int32, ("batch",)),
    }


def build_lowerable(cfg: ModelConfig, shape: ShapeSpec, ctx: ShardCtx):
    """→ (jitted fn, args tuple of specs)."""
    specs = input_specs(cfg, shape, ctx)
    if shape.kind == "train":
        import jax.numpy as _jnp
        ocfg = OptConfig(
            moments_dtype="int8" if cfg.name in BIG_MODELS else "float32")
        accum = _jnp.bfloat16 if cfg.name == "deepseek-v2-236b" else _jnp.float32
        mb = MICROBATCHES.get(cfg.name, 1)
        if "pod" in ctx.mesh.shape:      # per-device batch already halves
            mb = max(1, mb // 2)
        step = make_train_step(cfg, ocfg, ctx, microbatches=mb,
                               accum_dtype=accum)
        state = abstract_state(cfg, ctx, ocfg=ocfg)
        return jax.jit(step, donate_argnums=(0,)), (state, specs)
    pdefs_abstract = prm.abstract(
        __import__("repro.models.model", fromlist=["model_defs"]).model_defs(cfg), ctx)
    if shape.kind == "prefill":
        from repro.serve.prefill import prefill_step_fn
        step = prefill_step_fn(cfg, ctx)
        if cfg.enc_dec:
            return jax.jit(step), (pdefs_abstract, specs["frames"])
        if cfg.frontend != "none":
            return jax.jit(step), (pdefs_abstract, specs["tokens"],
                                   specs["frontend_embed"])
        return jax.jit(step), (pdefs_abstract, specs["tokens"])
    from repro.serve.decode import serve_step_fn
    step = serve_step_fn(cfg, ctx)
    return (jax.jit(step, donate_argnums=(1,)),
            (pdefs_abstract, specs["cache"], specs["tokens"], specs["pos"]))


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = OUT_DIR, force: bool = False) -> dict:
    mesh_name = "multi" if multi_pod else "single"
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "ok": False}
    ok, why = cell_supported(cfg, shape)
    if not ok:
        rec.update(skipped=True, reason=why, ok=True)
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
        return rec
    try:
        ctx = make_ctx(cfg, multi_pod, shape.kind)
        n_dev = ctx.mesh.size
        fn, args = build_lowerable(cfg, shape, ctx)
        t0 = time.time()
        with ctx.mesh:
            lowered = fn.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        txt = compiled.as_text()
        hlo = analyze_hlo(txt, n_dev)
        arg_b = getattr(ma, "argument_size_in_bytes", 0)
        out_b = getattr(ma, "output_size_in_bytes", 0)
        tmp_b = getattr(ma, "temp_size_in_bytes", 0)
        alias_b = getattr(ma, "alias_size_in_bytes", 0)
        peak = arg_b + out_b + tmp_b - alias_b
        rec.update(
            ok=True,
            lower_s=round(t1 - t0, 2), compile_s=round(t2 - t1, 2),
            n_devices=n_dev,
            memory={"argument_bytes": arg_b, "output_bytes": out_b,
                    "temp_bytes": tmp_b, "alias_bytes": alias_b,
                    "peak_bytes_per_device": peak,
                    "fits_hbm": bool(peak < HBM_BYTES),
                    "hbm_frac": round(peak / HBM_BYTES, 4)},
            cost_analysis={"flops_per_iter_hint": ca.get("flops", 0.0)},
            hlo=hlo,
            hlo_chars=len(txt),
        )
        print(f"[dryrun] {arch} {shape_name} {mesh_name}: compile "
              f"{t2 - t1:.1f}s peak/dev {peak/2**30:.2f} GiB "
              f"coll {hlo['collective_bytes_per_device']/2**20:.1f} MiB "
              f"dotflops {hlo['dot_flops_per_device']:.3e}")
    except Exception as e:  # record the failure — these are bugs to fix
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[dryrun] {arch} {shape_name} {mesh_name}: FAIL {e}")
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def all_cells():
    for arch in sorted(all_configs()):
        for shape_name in SHAPES:
            for mesh in ("single", "multi"):
                yield arch, shape_name, mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()
    if args.all:
        failures = 0
        for arch, shape_name, mesh in all_cells():
            out_path = os.path.join(
                args.out, f"{arch}__{shape_name}__{mesh}.json")
            if os.path.exists(out_path) and not args.force:
                continue
            # subprocess per cell: isolates XLA heap + survives crashes
            r = subprocess.run(
                [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                 "--shape", shape_name, "--mesh", mesh, "--out", args.out],
                env=dict(os.environ, PYTHONPATH=os.environ.get("PYTHONPATH", "src")),
                capture_output=True, text=True, timeout=3600)
            if r.returncode != 0:
                failures += 1
                print(f"[dryrun-all] {arch} {shape_name} {mesh} subprocess "
                      f"failed:\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
        print(f"[dryrun-all] done, {failures} subprocess failures")
        return
    assert args.arch and args.shape
    rec = run_cell(args.arch, args.shape, args.mesh == "multi", args.out,
                   args.force)
    sys.exit(0 if rec.get("ok") else 1)


if __name__ == "__main__":
    main()
