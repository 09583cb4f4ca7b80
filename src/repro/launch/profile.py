"""Compile one dry-run cell and report its top HLO ops by weighted cost.

The library half of ``experiments/profile_cell.py``: build the jitted
train/prefill/decode computation for an (arch, shape) cell on the
production mesh, and rank its fused HLO ops by weighted bytes / flops /
wire (``core.hlo_cost``).  Exposed both as the original experiment script
and through ``python -m repro.obs.cli profile`` so HLO cost profiling and
runtime span tracing live behind one front door.

Requires enough host devices for the production mesh — call
``ensure_host_devices()`` (or export ``XLA_FLAGS`` yourself) BEFORE the
first jax import of the process.
"""
from __future__ import annotations

import os
from typing import Dict, List, Tuple

__all__ = ["ensure_host_devices", "compile_cell", "profile_report",
           "format_report"]

_DEVICE_FLAG = "--xla_force_host_platform_device_count"


def ensure_host_devices(n: int = 512) -> None:
    """Append the host-device-count flag to ``XLA_FLAGS`` without
    clobbering whatever the caller already set there.  A pre-existing
    device-count flag wins (the user asked for that topology).  Must run
    before jax initializes its backends."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _DEVICE_FLAG in flags:
        return
    os.environ["XLA_FLAGS"] = f"{flags} {_DEVICE_FLAG}={n}".strip()


def compile_cell(arch: str, shape_name: str):
    """Lower + compile the cell's jitted computation; returns the compiled
    executable (``.as_text()`` is the optimized HLO)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..configs import get_config
    from ..core import hardware
    from ..core.config import RunConfig, get_shape
    from ..distributed import sharding as shd
    from ..models import build_model
    from ..optim import adamw_init, moment_shardings
    from . import dryrun as D
    from . import train as T
    from .mesh import make_production_mesh

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh()
    data = int(np.prod([mesh.shape[a] for a in ("pod", "data")
                        if a in mesh.shape]))
    micro = max(1, shape.global_batch // data) if shape.mode == "train" else 1
    tp = mesh.shape.get("model", 1)
    state_gb = cfg.param_count() * 4 * 3.3 / tp / 2 ** 30
    fsdp = shape.mode == "train" \
        and state_gb > 0.5 * (hardware.HBM_BYTES / 2 ** 30)
    run = RunConfig(microbatches=micro, fsdp=fsdp)
    model = build_model(cfg)
    with jax.set_mesh(mesh):
        rules = D.build_rules(mesh, cfg, shape, shape.mode, run)
        with shd.use_rules(rules):
            p_shapes, p_axes = D.abstract_params(model)
        if shape.mode in ("prefill", "decode"):
            p_shapes = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape,
                    jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
                p_shapes)
        p_sh = shd.tree_shardings_safe(p_axes, p_shapes, rules)
        specs = D.input_specs(cfg, shape)
        b_sh = D.batch_shardings(specs, rules)
        if shape.mode == "train":
            T.set_param_axes(p_axes)
            opt_shapes = jax.eval_shape(adamw_init, p_shapes)
            msh = moment_shardings(p_axes, jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), p_shapes),
                rules)
            opt_sh = type(opt_shapes)(step=jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()), m=msh, v=msh)
            comp = jax.jit(T.build_train_step(model, run, rules),
                           in_shardings=(p_sh, opt_sh, b_sh,
                                         jax.sharding.NamedSharding(
                                             mesh,
                                             jax.sharding.PartitionSpec())),
                           donate_argnums=(0, 1)).lower(
                p_shapes, opt_shapes, specs,
                jax.ShapeDtypeStruct((), jnp.int32)).compile()
        elif shape.mode == "prefill":
            def prefill_fn(params, batch):
                with shd.use_rules(rules):
                    return model.prefill(params, batch)
            comp = jax.jit(prefill_fn, in_shardings=(p_sh, b_sh)).lower(
                p_shapes, specs).compile()
        else:
            st_shapes, st_sh = D.state_specs(cfg, shape, rules)

            def decode_fn(params, state, tokens):
                with shd.use_rules(rules):
                    return model.decode_step(params, state, tokens)
            comp = jax.jit(decode_fn,
                           in_shardings=(p_sh, st_sh, b_sh["tokens"]),
                           donate_argnums=(1,)).lower(
                p_shapes, st_shapes, specs["tokens"]).compile()
    return comp


def profile_report(arch: str, shape_name: str, k: int = 10
                   ) -> Dict[str, List[Tuple[float, float, str]]]:
    """Compile the cell and return {by_bytes, by_flops, by_wire} top-op
    lists, each entry (weighted_cost, weight, hlo_line)."""
    from ..core.hlo_cost import top_costs
    comp = compile_cell(arch, shape_name)
    by_bytes, by_flops, by_wire = top_costs(comp.as_text(), k=k)
    return {"by_bytes": by_bytes, "by_flops": by_flops, "by_wire": by_wire}


def format_report(arch: str, shape_name: str,
                  report: Dict[str, List[Tuple[float, float, str]]]) -> str:
    lines = [f"=== {arch} {shape_name}: top weighted fused-bytes ops ==="]
    for wb, w, line in report["by_bytes"]:
        lines.append(f"{wb:.3e} (w={w:.0f}) {line[:120]}")
    lines.append("=== top weighted flops ===")
    for wf, w, line in report["by_flops"][:6]:
        lines.append(f"{wf:.3e} (w={w:.0f}) {line[:120]}")
    lines.append("=== top weighted wire ===")
    for ww, w, line in report["by_wire"][:8]:
        lines.append(f"{ww:.3e} (w={w:.0f}) {line[:120]}")
    return "\n".join(lines)
