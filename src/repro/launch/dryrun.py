import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell on the production meshes and derive the three-term roofline.

The two lines above MUST stay first: jax locks the device count at first
init, and the dry-run needs 512 placeholder host devices to build the
(2, 16, 16) multi-pod mesh.  Tests/benches never import this module.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-67b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all            # 40-cell grid
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

Each cell writes experiments/dryrun/<arch>__<shape>__<mesh>.json with the
memory analysis, loop-aware cost terms, collective schedule, and roofline
fractions (EXPERIMENTS.md SS Dry-run / SS Roofline read these)."""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import hardware, roofline
from ..core.config import ArchConfig, RunConfig, ShapeConfig, get_shape, SHAPES
from ..distributed import sharding as shd
from ..models import build_model
from ..models import transformer as tfm
from ..models import encdec as encdec_mod
from ..optim import adamw_init, moment_shardings
from . import train as train_mod
from .mesh import make_production_mesh, mesh_name

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

WHISPER_ENC_DECODE = 1500


# ---------------------------------------------------------------------------
# Rules per mode
# ---------------------------------------------------------------------------

def build_rules(mesh, cfg: ArchConfig, shape: ShapeConfig, mode: str,
                run: RunConfig):
    tp = mesh.shape.get("model", 1)
    shard_kv = cfg.n_kv_heads % tp == 0
    rules = shd.default_rules(mesh, shard_kv=shard_kv, fsdp=run.fsdp,
                              seq_shard=run.seq_shard)
    r = dict(rules.rules)
    data_axes = r["batch"]
    dsize = int(np.prod([mesh.shape[a] for a in data_axes])) \
        if data_axes else 1
    if shape.global_batch % dsize != 0 or shape.global_batch < dsize:
        r["batch"] = None          # e.g. long_500k's global_batch=1
    # KV-cache length axis: sharded over "model" for serving modes (the
    # 687 GB decode_32k caches do not fit any other way).  NOTE: "heads"
    # stays on "model" in every mode — head padding is derived from the
    # rules, so init and all apply modes must agree on it.
    r["kvlen"] = "model" if mode in ("prefill", "decode") else None
    return shd.ShardingRules(mesh, r)


# ---------------------------------------------------------------------------
# Abstract params / inputs
# ---------------------------------------------------------------------------

def abstract_params(model) -> Tuple[Any, Any]:
    """(ShapeDtypeStruct tree, logical-axes tree) without allocating."""
    box = {}

    def init_vals(key):
        vals, axes = shd.split_tree(model.init(key))
        box["axes"] = axes
        return vals

    shapes = jax.eval_shape(init_vals, jax.random.PRNGKey(0))
    return shapes, box["axes"]


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f32 = jnp.float32
    if shape.mode == "decode":
        return {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}
    if cfg.is_encdec:
        n_dec = s // 2
        return {
            "tokens": jax.ShapeDtypeStruct((b, n_dec), i32),
            "labels": jax.ShapeDtypeStruct((b, n_dec), i32),
            "frames": jax.ShapeDtypeStruct((b, s - n_dec, cfg.d_model), f32),
        }
    n_text = s - (cfg.n_patches or 0)
    specs = {
        "tokens": jax.ShapeDtypeStruct((b, n_text), i32),
        "labels": jax.ShapeDtypeStruct((b, n_text), i32),
    }
    if cfg.n_patches:
        specs["patches"] = jax.ShapeDtypeStruct(
            (b, cfg.n_patches, cfg.d_model), f32)
    if shape.mode == "prefill":
        specs.pop("labels")
    return specs


def batch_shardings(specs: Dict[str, Any], rules) -> Dict[str, Any]:
    out = {}
    for k, v in specs.items():
        axes = ("batch",) + (None,) * (len(v.shape) - 1)
        out[k] = rules.sharding(axes)
    return out


def state_specs(cfg: ArchConfig, shape: ShapeConfig, rules):
    """(ShapeDtypeStruct state, shardings) for decode cells."""
    b, s = shape.global_batch, shape.seq_len
    if cfg.is_encdec:
        st = jax.eval_shape(
            lambda: encdec_mod.encdec_state_init(
                cfg, b, s, WHISPER_ENC_DECODE, jnp.dtype(cfg.dtype)))
        axes = encdec_mod.encdec_state_axes()
    else:
        st = jax.eval_shape(
            lambda: tfm.init_state(cfg, b, s, jnp.dtype(cfg.dtype)))
        axes = tfm.state_axes()
    shardings = jax.tree.map(
        lambda spec, ax: jax.sharding.NamedSharding(
            rules.mesh,
            shd.safe_spec(rules, _pad_axes(ax, len(spec.shape)), spec.shape)),
        st, axes,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return st, shardings


def _pad_axes(ax, ndim):
    ax = tuple(ax)
    return ax + (None,) * (ndim - len(ax))


# ---------------------------------------------------------------------------
# Analytic useful-flops model
# ---------------------------------------------------------------------------

def useful_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """6ND (train) / 2ND (inference) + attention term, whole job."""
    n = cfg.active_param_count()
    b, s = shape.global_batch, shape.seq_len
    if shape.mode == "decode":
        tokens = b                                # one token per sequence
        flops = 2.0 * n * tokens
        # decode attention reads the cache: 4 * L * H*hd * S_ctx per token
        if cfg.family not in ("ssm",):
            ctx = min(s, cfg.attn.window) if cfg.attn.window else s
            flops += 4.0 * cfg.n_layers * cfg.n_heads * cfg.head_dim_ \
                * ctx * tokens
        return flops
    tokens = b * (s if not cfg.is_encdec else s // 2)
    mult = 6.0 if shape.mode == "train" else 2.0
    flops = mult * n * tokens
    if cfg.family != "ssm":
        ctx = min(s, cfg.attn.window) if cfg.attn.window else s
        # causal: half the S x S rectangle; x2 matmuls (qk, pv)
        att = 2.0 * cfg.n_layers * cfg.n_heads * cfg.head_dim_ * s * ctx * b
        if not cfg.attn.window:
            att *= 0.5
        flops += att * (3.0 if shape.mode == "train" else 1.0)
    return flops


def skip_reason(cfg: ArchConfig, shape: ShapeConfig) -> Optional[str]:
    if shape.name == "long_500k" and not (
            cfg.attn.sub_quadratic or cfg.family == "ssm"):
        return ("full quadratic attention at 524k tokens — skipped per the "
                "assignment; see DESIGN.md §Arch-applicability")
    return None


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               run: Optional[RunConfig] = None,
               cfg: Optional[ArchConfig] = None) -> Dict[str, Any]:
    from ..configs import get_config
    cfg = cfg or get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mname = mesh_name(mesh)
    n_chips = int(np.prod(list(mesh.shape.values())))
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mname,
        "mode": shape.mode, "n_chips": n_chips,
        "multi_pod": multi_pod,
    }
    reason = skip_reason(cfg, shape)
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec

    if run is None:
        data = int(np.prod([mesh.shape[a] for a in ("pod", "data")
                            if a in mesh.shape]))
        micro = max(1, shape.global_batch // data) if shape.mode == "train" \
            else 1
        # auto-FSDP: fp32 params + grads + accumulator + moments live
        # per-chip; shard them over the data axes when TP alone won't fit
        tp = mesh.shape.get("model", 1)
        state_gb = cfg.param_count() * 4 * 3.3 / tp / 2 ** 30
        fsdp = shape.mode == "train" and state_gb > 0.5 * (
            hardware.HBM_BYTES / 2 ** 30)
        run = RunConfig(microbatches=micro, fsdp=fsdp,
                        grad_compression="bf16")
    rec["microbatches"] = run.microbatches
    rec["fsdp"] = run.fsdp

    model = build_model(cfg)
    t0 = time.time()
    with jax.set_mesh(mesh):
        mode = shape.mode
        rules = build_rules(mesh, cfg, shape, mode, run)
        with shd.use_rules(rules):
            # init under the same rules: head/vocab/expert padding is
            # derived from the rules and must match between init and apply
            p_shapes, p_axes = abstract_params(model)
        if mode in ("prefill", "decode"):
            # serving deployments hold bf16 weights
            p_shapes = jax.tree.map(
                lambda s_: jax.ShapeDtypeStruct(
                    s_.shape, jnp.bfloat16 if s_.dtype == jnp.float32
                    else s_.dtype), p_shapes)
        p_shardings = shd.tree_shardings_safe(p_axes, p_shapes, rules)
        specs = input_specs(cfg, shape)
        b_shardings = batch_shardings(specs, rules)

        if mode == "train":
            train_mod.set_param_axes(p_axes)
            opt_shapes = jax.eval_shape(adamw_init, p_shapes)
            mshard = moment_shardings(
                p_axes, jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                    p_shapes), rules)
            opt_shardings = type(opt_shapes)(
                step=jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()),
                m=mshard, v=mshard)
            step_fn = train_mod.build_train_step(model, run, rules)
            lowered = jax.jit(
                step_fn,
                in_shardings=(p_shardings, opt_shardings, b_shardings,
                              jax.sharding.NamedSharding(
                                  mesh, jax.sharding.PartitionSpec())),
                donate_argnums=(0, 1),
            ).lower(p_shapes, opt_shapes, specs,
                    jax.ShapeDtypeStruct((), jnp.int32))
        elif mode == "prefill":
            def prefill_fn(params, batch):
                with shd.use_rules(rules):
                    return model.prefill(params, batch)
            lowered = jax.jit(
                prefill_fn, in_shardings=(p_shardings, b_shardings),
            ).lower(p_shapes, specs)
        else:  # decode
            st_shapes, st_shardings = state_specs(cfg, shape, rules)
            def decode_fn(params, state, tokens):
                with shd.use_rules(rules):
                    return model.decode_step(params, state, tokens)
            lowered = jax.jit(
                decode_fn,
                in_shardings=(p_shardings, st_shardings,
                              b_shardings["tokens"]),
                donate_argnums=(1,),
            ).lower(p_shapes, st_shapes, specs["tokens"])

        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    mem = compiled.memory_analysis()
    rep = roofline.analyze_compiled(
        compiled, arch=arch, shape=shape_name, mesh_name=mname,
        n_chips=n_chips, model_flops_total=useful_flops(cfg, shape),
        memory=mem)
    # train/decode donate their big inputs: outputs alias args, so the peak
    # is max(args, out) + temps; prefill creates a fresh state (no aliasing)
    if mode in ("train", "decode"):
        peak = max(rep.arg_bytes, rep.out_bytes) + rep.temp_bytes
    else:
        peak = rep.arg_bytes + rep.out_bytes + rep.temp_bytes
    rec.update(
        status="ok",
        params=cfg.param_count(),
        active_params=cfg.active_param_count(),
        hbm_per_chip_gb=round(peak / 2 ** 30, 3),
        arg_bytes=rep.arg_bytes, temp_bytes=rep.temp_bytes,
        out_bytes=rep.out_bytes,
        fits_hbm=peak <= hardware.HBM_BYTES,
        hlo_flops=rep.hlo_flops, hlo_bytes=rep.hlo_bytes,
        hlo_bytes_upper=rep.hlo_bytes_upper,
        collective_wire_bytes=rep.collective_wire_bytes,
        collective_counts=rep.collective_counts,
        collective_bytes_by_kind=rep.collective_bytes_by_kind,
        model_flops_per_chip=rep.model_flops,
        t_compute=rep.t_compute, t_memory=rep.t_memory,
        t_collective=rep.t_collective, bottleneck=rep.bottleneck,
        useful_flops_ratio=rep.useful_flops_ratio,
        roofline_fraction=rep.roofline_fraction,
    )
    return rec


def save_record(rec: Dict[str, Any], out_dir: str = OUT_DIR):
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1, sort_keys=True, default=str)
    return os.path.join(out_dir, name)


def summarize(rec: Dict[str, Any]) -> str:
    if rec.get("status") == "skipped":
        return (f"{rec['arch']:>20s} {rec['shape']:<12s} {rec['mesh']:<9s} "
                f"SKIPPED: {rec['reason'][:60]}")
    if rec.get("status") != "ok":
        return (f"{rec['arch']:>20s} {rec['shape']:<12s} {rec['mesh']:<9s} "
                f"FAILED: {rec.get('error', '?')[:80]}")
    return (f"{rec['arch']:>20s} {rec['shape']:<12s} {rec['mesh']:<9s} "
            f"hbm={rec['hbm_per_chip_gb']:6.2f}G "
            f"tc={rec['t_compute']*1e3:8.2f}ms "
            f"tm={rec['t_memory']*1e3:8.2f}ms "
            f"tx={rec['t_collective']*1e3:8.2f}ms "
            f"{rec['bottleneck']:<10s} "
            f"useful={rec['useful_flops_ratio']*100:5.1f}% "
            f"roof={rec['roofline_fraction']*100:5.1f}% "
            f"[{rec['compile_s']:.0f}s]")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    from ..configs import ARCH_NAMES
    archs = ARCH_NAMES if (args.all or not args.arch) else [args.arch]
    shapes = [s.name for s in SHAPES] if (args.all or not args.shape) \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                try:
                    rec = lower_cell(arch, shape, multi_pod=mp)
                except Exception as e:          # record, keep going
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "failed", "error": repr(e),
                           "traceback": traceback.format_exc()}
                    failures += 1
                save_record(rec, args.out)
                print(summarize(rec), flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
