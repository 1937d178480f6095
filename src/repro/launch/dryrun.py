"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
cell on 512 placeholder host devices and extract the roofline terms.

MUST be run as a module: ``PYTHONPATH=src python -m repro.launch.dryrun
--arch starcoder2-3b --shape train_4k --mesh pod``; ``--all`` sweeps every
cell and writes JSON results for EXPERIMENTS.md.

The XLA_FLAGS export below must run before ANY jax initialization —
importing this module from an already-initialized process will not get
the 512 placeholder devices.
"""

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
from typing import Dict, Optional, Tuple  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402

from repro import configs as C                       # noqa: E402
from repro.models import build_model                 # noqa: E402
from repro.optim import AdamWConfig, adamw_init      # noqa: E402
from repro.launch import sharding as SH              # noqa: E402
from repro.launch.mesh import make_production_mesh, dp_axes, axis_size  # noqa: E402
from repro.launch.steps import (make_train_step, make_prefill_step,     # noqa: E402
                                make_decode_step)

# ---------------------------------------------------------------------------
# hardware constants (TPU v5e-class target; see EXPERIMENTS.md §Roofline)
# ---------------------------------------------------------------------------
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per chip (per-link, conservative)

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|"
                       r"pred|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(m) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes of every collective op in the (post-SPMD) HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for op in _COLLECTIVES:
            # match ` = TYPE[SHAPE] op-name(` and `op-name-start(`
            if re.search(rf"= [^=]*\b{op}(-start)?\(", stripped):
                # operand shapes: inside the call parens
                call = stripped.split(f"{op}", 1)[1]
                total = sum(_shape_bytes(m)
                            for m in _SHAPE_RE.finditer(call))
                if total == 0:
                    # fall back to the output shape (lhs)
                    m = _SHAPE_RE.search(stripped)
                    total = _shape_bytes(m) if m else 0
                out[op] += total
                break
    return out


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------


def cell_config(arch: str, **overrides):
    """Full config tuned for the dry-run: bf16 params (+bf16 moments via the
    optimizer config) — the production numerics for the giant models."""
    base = dict(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                remat="full", scan_layers=True)
    base.update(overrides)
    return C.get_config(arch, **base)


# ---------------------------------------------------------------------------
# FLOPs methodology (see EXPERIMENTS.md §Roofline):
#
# XLA's cost_analysis counts a while-loop body ONCE, not x trip-count, so
# (a) the layer stack is UNROLLED for the cost pass — two reduced depths
#     (L1, L2) are compiled and metrics extrapolated linearly in L (exact
#     for homogeneous stacks; compile stays bounded for 95-layer configs);
# (b) the remaining inner scans (the flash-attention k/q block loops and
#     the rwkv/ssd time-step recurrences) are corrected with closed-form
#     totals below (the hlo already contains ~1/n_blocks of them; that
#     residue is the documented <2% error).
# Memory fit is measured separately on the scanned full-depth compile
# (buffer reuse there matches TPU reality; CPU buffer assignment of huge
# unrolled graphs is pessimistic).
# ---------------------------------------------------------------------------


def _reduced_depths(cfg) -> Tuple[int, int]:
    if cfg.family == "vlm":
        e = cfg.cross_attn_every
        return 2 * e, 4 * e
    if cfg.family == "hybrid":
        e = max(cfg.attn_every, 1)
        return 2 * e, 4 * e
    if cfg.family == "moe":
        return 4, 8
    return 8, 16


def analytic_scan_corrections(cfg, shape: C.Shape) -> float:
    """Closed-form FLOPs of the inner scans (per full model), to ADD to the
    unrolled-layer hlo FLOPs. Factors: fwd attention = 2 matmuls; train =
    fwd + remat recompute + 5-matmul flash bwd = 18 matmul-halves."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        return 0.0  # decode paths have no inner scans
    train = shape.kind == "train"
    total = 0.0
    H, hd = cfg.n_heads, cfg.hd
    attn_unit = 2.0 * B * H * hd * float(S) * float(S)  # one S x S matmul
    attn_factor = 9.0 if train else 2.0                 # in units of 2BHS^2hd
    if cfg.family in ("dense", "audio", "moe"):
        total += cfg.n_layers * attn_factor * attn_unit
    elif cfg.family == "vlm":
        n_groups = cfg.n_layers // cfg.cross_attn_every
        n_self = cfg.n_layers - n_groups
        total += n_self * attn_factor * attn_unit
        cross_unit = 2.0 * B * H * hd * float(S) * float(cfg.n_image_tokens)
        total += n_groups * attn_factor * cross_unit
    elif cfg.family == "rwkv6":
        N = cfg.rwkv_head_dim
        Hr = cfg.d_model // N
        per_step = 10.0 * B * Hr * N * N
        factor = 4.0 if train else 1.0
        total += cfg.n_layers * factor * per_step * S
    elif cfg.family == "hybrid":
        d_in = cfg.ssm_expand * cfg.d_model
        Hm = d_in // cfg.ssm_head_dim
        per_step = 8.0 * B * Hm * cfg.ssm_head_dim * cfg.ssm_state
        factor = 4.0 if train else 1.0
        total += cfg.n_layers * factor * per_step * S
        n_groups = cfg.n_layers // max(cfg.attn_every, 1)
        total += n_groups * attn_factor * attn_unit
    return total


def input_sds(cfg, shape: C.Shape, model) -> Tuple[Dict, Optional[Dict]]:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S + 1), jnp.int32)}
        if cfg.family == "vlm":
            batch["image_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.n_image_tokens, cfg.d_model), jnp.bfloat16)
        return batch, None
    if shape.kind == "prefill":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if cfg.family == "vlm":
            batch["image_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.n_image_tokens, cfg.d_model), jnp.bfloat16)
        return batch, None
    # decode: one new token with a KV cache of seq_len
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    cache = jax.eval_shape(lambda: model.init_cache(B, S))
    return {"tokens": tokens}, cache


def _compile_pass(cfg, shape: C.Shape, mesh,
                  opt_overrides: Optional[dict] = None) -> Dict:
    """Lower + compile one variant; return raw metrics."""
    model = build_model(cfg)
    out: Dict = {}
    t0 = time.time()
    params_sds = jax.eval_shape(lambda k: model.init(k),
                                jax.ShapeDtypeStruct((2,), jnp.uint32))
    pspecs = SH.param_specs(cfg, params_sds, mesh)
    p_shard = SH.to_named(pspecs, mesh)
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_cfg = AdamWConfig(moment_dtype=jnp.bfloat16,
                                  **(opt_overrides or {}))
            opt_sds = jax.eval_shape(
                lambda p: adamw_init(p, opt_cfg), params_sds)
            ospecs = SH.opt_specs(cfg, opt_sds, pspecs, mesh)
            o_shard = SH.to_named(ospecs, mesh)
            batch_sds, _ = input_sds(cfg, shape, model)
            bspecs = SH.batch_specs(cfg, mesh, shape.global_batch)
            b_shard = SH.to_named({k: bspecs[k] for k in batch_sds}, mesh)
            step = make_train_step(model, opt_cfg)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, o_shard, b_shard),
                             out_shardings=(p_shard, o_shard, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_sds, opt_sds, batch_sds)
        elif shape.kind == "prefill":
            batch_sds, _ = input_sds(cfg, shape, model)
            bspecs = SH.batch_specs(cfg, mesh, shape.global_batch)
            b_shard = SH.to_named({k: bspecs[k] for k in batch_sds}, mesh)
            cache_sds = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch,
                                         shape.seq_len + 1))
            cspecs = SH.cache_specs(cfg, cache_sds, mesh, shape.global_batch)
            logits_spec = SH.to_named(
                jax.sharding.PartitionSpec(None, None, "model"), mesh)
            step = make_prefill_step(model)
            jitted = jax.jit(step, in_shardings=(p_shard, b_shard),
                             out_shardings=(logits_spec,
                                            SH.to_named(cspecs, mesh)))
            lowered = jitted.lower(params_sds, batch_sds)
        else:  # decode
            inp, cache_sds = input_sds(cfg, shape, model)
            cspecs = SH.cache_specs(cfg, cache_sds, mesh, shape.global_batch)
            c_shard = SH.to_named(cspecs, mesh)
            dp = dp_axes(mesh)
            dp_ok = (shape.global_batch % axis_size(mesh, dp) == 0
                     and shape.global_batch > 1)
            tok_spec = SH.to_named(jax.sharding.PartitionSpec(
                dp if dp_ok else None, None), mesh)
            logits_spec = SH.to_named(
                jax.sharding.PartitionSpec(None, None, "model"), mesh)
            step = make_decode_step(model)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, c_shard, tok_spec),
                             out_shardings=(logits_spec, c_shard),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_sds, cache_sds, inp["tokens"])

        out["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        out["compile_s"] = round(time.time() - t1, 2)
        try:
            mem = compiled.memory_analysis()
            out["memory"] = {
                k: int(getattr(mem, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes",
                          "generated_code_size_in_bytes")
                if hasattr(mem, k)}
        except Exception as e:  # pragma: no cover
            out["memory_error"] = str(e)
        try:
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            out["hlo_flops"] = float(cost.get("flops", 0.0))
            out["hlo_bytes"] = float(cost.get("bytes accessed", 0.0))
        except Exception as e:  # pragma: no cover
            out["cost_error"] = str(e)
        coll = collective_bytes(compiled.as_text())
        out["collective_bytes"] = coll
        out["collective_total"] = int(sum(coll.values()))
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             opt_overrides: Optional[dict] = None,
             cfg_overrides: Optional[dict] = None,
             scan_layers: bool = False,
             skip_cost_pass: bool = False,
             verbose: bool = True) -> Dict:
    """One dry-run cell: a scanned full-depth MEMORY pass (+ sharding /
    compile validation — this is the pass that must succeed for the
    multi-pod requirement) and an unrolled COST pass with two reduced
    depths extrapolated linearly in L (see module docstring)."""
    shape = C.SHAPES[shape_name]
    ok, why = C.shape_applicable(arch, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multipod" if multi_pod else "pod",
                "skipped": True, "reason": why}
    cfg_overrides = dict(cfg_overrides or {})
    cfg = cell_config(arch, **cfg_overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    result = {"arch": arch, "shape": shape_name,
              "mesh": "multipod" if multi_pod else "pod",
              "n_chips": n_chips, "skipped": False,
              "params": int(cfg.param_count()),
              "active_params": int(cfg.active_param_count())}

    # ---- pass B: scanned, full depth — memory / sharding validation ----
    mem_pass = _compile_pass(cell_config(arch, scan_layers=True,
                                         **cfg_overrides),
                             shape, mesh, opt_overrides)
    result["lower_s"] = mem_pass["lower_s"]
    result["compile_s"] = mem_pass["compile_s"]
    result["memory"] = mem_pass.get("memory", {})
    args_b = result["memory"].get("argument_size_in_bytes", 0)
    temp_b = result["memory"].get("temp_size_in_bytes", 0)
    result["bytes_per_device"] = int(args_b + temp_b)
    result["fits_16gb_hbm"] = bool(result["bytes_per_device"] < 16e9)

    # ---- pass A: unrolled cost extrapolation ---------------------------
    if not skip_cost_pass:
        L = cfg.n_layers
        L1, L2 = _reduced_depths(cfg)
        if L <= max(L2, 24):
            cost = _compile_pass(cell_config(arch, scan_layers=False,
                                             **cfg_overrides),
                                 shape, mesh, opt_overrides)
            flops, byts = cost.get("hlo_flops", 0.), cost.get("hlo_bytes", 0.)
            coll = float(cost["collective_total"])
            result["cost_compile_s"] = cost["compile_s"]
            result["cost_mode"] = "full_unroll"
        else:
            c1 = _compile_pass(
                cell_config(arch, scan_layers=False, n_layers=L1,
                            **cfg_overrides), shape, mesh, opt_overrides)
            c2 = _compile_pass(
                cell_config(arch, scan_layers=False, n_layers=L2,
                            **cfg_overrides), shape, mesh, opt_overrides)

            def extrap(k):
                v1, v2 = float(c1.get(k, 0.0)), float(c2.get(k, 0.0))
                per_layer = (v2 - v1) / (L2 - L1)
                return max(v1 + per_layer * (L - L1), 0.0)
            flops = extrap("hlo_flops")
            byts = extrap("hlo_bytes")
            coll = extrap("collective_total")
            result["cost_compile_s"] = c1["compile_s"] + c2["compile_s"]
            result["cost_mode"] = f"extrapolated_L{L1}_L{L2}"
        # per-device -> global
        correction = analytic_scan_corrections(cfg, shape)
        result["hlo_flops_raw_per_dev"] = flops
        result["hlo_flops"] = flops * n_chips + correction
        result["scan_correction_flops"] = correction
        result["hlo_bytes"] = byts * n_chips
        result["collective_total"] = int(coll)

        # ---- roofline terms (§Roofline) --------------------------------
        result["t_compute_s"] = result["hlo_flops"] / (n_chips * PEAK_FLOPS)
        result["t_memory_s"] = result["hlo_bytes"] / (n_chips * HBM_BW)
        result["t_collective_s"] = result["collective_total"] / (
            n_chips * ICI_BW)
        terms = {"compute": result["t_compute_s"],
                 "memory": result["t_memory_s"],
                 "collective": result["t_collective_s"]}
        result["bottleneck"] = max(terms, key=terms.get)
        n_tokens = shape.global_batch * (
            shape.seq_len if shape.kind in ("train", "prefill") else 1)
        if shape.kind == "train":
            model_flops = 6.0 * cfg.active_param_count() * n_tokens
        else:
            model_flops = 2.0 * cfg.active_param_count() * n_tokens
        result["model_flops"] = model_flops
        result["useful_flops_ratio"] = (
            model_flops / result["hlo_flops"] if result["hlo_flops"] else 0.0)
        bound = max(terms.values())
        result["roofline_fraction"] = (
            model_flops / (n_chips * PEAK_FLOPS)) / bound if bound else 0.0
    if verbose:
        print(json.dumps(result, indent=2, default=str), flush=True)
    return result


def main() -> None:
    """CLI entry point: run one (arch x shape x mesh) cell, or ``--all``
    to sweep the full matrix and write JSON for EXPERIMENTS.md."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(C.SHAPES) + [None])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch x shape) cell")
    ap.add_argument("--out", default=None, help="write JSON results here")
    ap.add_argument("--scan-layers", action="store_true",
                    help="scan instead of unroll (fast compile; FLOPs "
                         "undercounted by XLA's while-body-once rule)")
    ap.add_argument("--skip-cost", action="store_true",
                    help="memory/sharding validation pass only (used for "
                         "the multipod sweep; the roofline table is "
                         "single-pod per the assignment)")
    args = ap.parse_args()

    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    results = []
    if args.all:
        cells = [(a, s.name) for a, s, ok, _ in C.cells(include_skipped=True)]
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]
    for arch, shape_name in cells:
        for mp in meshes:
            try:
                res = run_cell(arch, shape_name, mp,
                               scan_layers=args.scan_layers,
                               skip_cost_pass=args.skip_cost)
            except Exception as e:
                res = {"arch": arch, "shape": shape_name,
                       "mesh": "multipod" if mp else "pod",
                       "error": f"{type(e).__name__}: {e}"}
                print(json.dumps(res), flush=True)
            results.append(res)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2, default=str)
    n_err = sum(1 for r in results if r.get("error"))
    n_skip = sum(1 for r in results if r.get("skipped"))
    print(f"\ndry-run: {len(results)} cells, {n_skip} skipped (documented), "
          f"{n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
