import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh)
combination and extract memory / cost / collective analysis (deliverable e).

The two lines ABOVE the docstring must run before any jax import — jax locks
the device count on first init. 512 placeholder host devices back both
production meshes (16×16 single-pod uses the first 256).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch all [--multi-pod] \
      [--head l2s] [--json out.json]
"""
import argparse
import json
import sys
import time
from dataclasses import asdict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, L2SConfig, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.data.loader import input_specs
from repro.launch.mesh import data_axes, make_production_mesh, mesh_axis_sizes
from repro.launch.roofline import roofline_from_compiled
from repro.launch.sharding import (batch_shardings, cache_shardings,
                                   params_shardings, replicated,
                                   screen_shardings)
from repro.launch.steps import (abstract_cache, abstract_opt_state,
                                abstract_params, abstract_screen,
                                default_microbatches, make_prefill_step,
                                make_serve_step, make_train_step)
from repro.models.model import build_model
from repro.configs.base import TrainConfig

# the chip the production meshes are made of (a key of mesh.CHIP_PEAKS)
TARGET_DEVICE_KIND = "TPU v5 lite"

# long_500k on pure full-attention dense archs runs the documented
# sliding-window DECODE VARIANT (DESIGN §5) — ring-buffer cache of this size.
SWA_VARIANT_WINDOW = 4096


def applicable(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    if shape.kind == "decode" and not cfg.supports_decode:
        return False, "encoder-only: no decode step (DESIGN §5)"
    return True, ""


def decode_window(cfg: ModelConfig, shape: ShapeConfig):
    """(window, variant_tag) for decode shapes."""
    if shape.name != "long_500k":
        return cfg.sliding_window, ""
    if cfg.supports_long_context():
        return cfg.sliding_window, ""
    return SWA_VARIANT_WINDOW, "swa-variant"


def lower_combo(cfg: ModelConfig, shape: ShapeConfig, mesh, head: str = "full",
                expert_parallel: bool | None = None,
                fsdp: bool = True, loss_chunk=None, serve_2d: bool = False):
    """serve_2d: weight-stationary decode — batch replicated, KV cache
    sequence-sharded over ALL mesh axes, weights 2D-sharded and never
    gathered (contractions psum small decode activations instead). See
    EXPERIMENTS.md §Perf HC1 iteration 3."""
    """Lower + compile one combination. Returns a result record dict."""
    model = build_model(cfg)
    aparams = abstract_params(model)
    if expert_parallel is None:
        # auto: expert-parallel when experts divide the model axis
        expert_parallel = (cfg.moe is not None and
                           cfg.moe.num_experts % mesh_axis_sizes(mesh)["model"] == 0)
    psh = params_shardings(mesh, cfg, aparams, expert_parallel=expert_parallel,
                           fsdp=fsdp)
    specs = input_specs(cfg, shape)
    bsh = batch_shardings(mesh, cfg, specs)

    t0 = time.time()
    if shape.kind == "train":
        dsize = int(np.prod([mesh_axis_sizes(mesh)[a] for a in data_axes(mesh)]))
        mb = default_microbatches(cfg, shape.global_batch, shape.seq_len, dsize)
        tcfg = TrainConfig(microbatch=mb)
        step = make_train_step(model, tcfg)
        aopt = abstract_opt_state(aparams)
        osh = _opt_shardings(aopt, psh, mesh)
        metrics_sh = replicated(mesh, {"loss": 0, "gnorm": 0})
        with mesh:
            lowered = jax.jit(
                step,
                in_shardings=(psh, osh, bsh),
                out_shardings=(psh, osh, metrics_sh),
            ).lower(aparams, aopt, specs)
    elif shape.kind == "prefill":
        step = make_prefill_step(model)
        out_sh = (NamedSharding(mesh, P(data_axes(mesh))),) * 2
        with mesh:
            lowered = jax.jit(
                step, in_shardings=(psh, bsh), out_shardings=out_sh,
            ).lower(aparams, specs)
    else:  # decode
        window, variant = decode_window(cfg, shape)
        acache = abstract_cache(model, shape.global_batch, shape.seq_len,
                                window=window)
        csh = cache_shardings(mesh, cfg, acache, force_seq_shard=serve_2d)
        tok_sh = NamedSharding(mesh, P()) if serve_2d else bsh["token"]
        pos_sh = NamedSharding(mesh, P())
        B = shape.global_batch
        dsize = int(np.prod([mesh_axis_sizes(mesh)[a] for a in data_axes(mesh)]))
        out_vec_sh = NamedSharding(mesh, P(data_axes(mesh)) if B % dsize == 0
                                   and B > 1 and not serve_2d else P())
        if head == "l2s":
            ascreen = abstract_screen(cfg, L2SConfig())
            ssh = screen_shardings(mesh, ascreen)
            step = make_serve_step(model, head="l2s", window=window)
            with mesh:
                lowered = jax.jit(
                    step,
                    in_shardings=(psh, ssh[0], ssh[1], csh, tok_sh, pos_sh),
                    out_shardings=(out_vec_sh, out_vec_sh, csh),
                ).lower(aparams, *ascreen, acache, specs["token"], specs["pos"])
        else:
            step = make_serve_step(model, head="full", window=window)
            with mesh:
                lowered = jax.jit(
                    step,
                    in_shardings=(psh, csh, tok_sh, pos_sh),
                    out_shardings=(out_vec_sh, out_vec_sh, csh),
                ).lower(aparams, acache, specs["token"], specs["pos"])
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    rec = {
        "arch": cfg.name, "shape": shape.name, "head": head,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    }
    if shape.kind == "decode":
        window, variant = decode_window(cfg, shape)
        if variant:
            rec["variant"] = variant
    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "code_bytes": int(getattr(ma, "generated_code_size_in_bytes", 0)),
        }
    except Exception as e:  # CPU backend may not implement it
        rec["memory"] = {"error": str(e)[:120]}
    try:
        rl = roofline_from_compiled(compiled, TARGET_DEVICE_KIND)
        rec["roofline"] = rl.as_dict()
    except Exception as e:
        rec["roofline"] = {"error": str(e)[:120]}
    return rec


def _opt_shardings(aopt, psh, mesh):
    """AdamW state: moments mirror the param shardings; step replicated."""
    from repro.optim.adamw import AdamWState
    return AdamWState(step=NamedSharding(mesh, P()), mu=psh, nu=psh)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all",
                    choices=["all"] + list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--head", default="full", choices=["full", "l2s"])
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--serve-2d", action="store_true",
                    help="weight-stationary 2D decode sharding (see §Perf)")
    ap.add_argument("--json", default=None, help="append records to this file")
    args = ap.parse_args(argv)

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    archs = list(ASSIGNED_ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]

    records = []
    for a in archs:
        cfg = get_config(a)
        for s in shapes:
            shape = INPUT_SHAPES[s]
            ok, why = applicable(cfg, shape)
            if not ok:
                rec = {"arch": a, "shape": s, "skipped": why,
                       "mesh": "x".join(str(x) for x in mesh.devices.shape)}
                print(json.dumps(rec))
                records.append(rec)
                continue
            if args.head == "l2s" and shape.kind != "decode":
                continue
            try:
                rec = lower_combo(cfg, shape, mesh, head=args.head,
                                  fsdp=not args.no_fsdp,
                                  serve_2d=args.serve_2d)
            except Exception as e:
                rec = {"arch": a, "shape": s, "head": args.head,
                       "error": f"{type(e).__name__}: {e}"[:300]}
            print(json.dumps(rec))
            records.append(rec)
    if args.json:
        with open(args.json, "a") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
    errs = [r for r in records if "error" in r]
    print(f"\n[dryrun] {len(records)} combos, {len(errs)} errors", file=sys.stderr)
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
