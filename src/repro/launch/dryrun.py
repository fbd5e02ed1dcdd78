"""Multi-pod dry run: lower + compile every (arch × shape × mesh) combo.

Proves the distribution config is coherent without hardware: builds the
production mesh from 512 host placeholder devices, lowers the real step
functions (train_step / prefill / serve_step) against abstract inputs with
the production shardings, compiles, and records memory/cost/collective
statistics for the roofline analysis.

Usage:
  python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape decode_32k \
      --mesh single [--out results.jsonl]
  python -m repro.launch.dryrun --all --mesh both
"""
# The first two lines of real work: force 512 host devices BEFORE any jax
# device-state initialization (this module must be imported first).
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))
# §Perf T1: pad-shard attention-head activations over the model axis
os.environ.setdefault("REPRO_SHARD_HEADS_AXIS", "model")
# §Perf T3: sequence-parallel residual stream between blocks
os.environ.setdefault("REPRO_SHARD_SEQ_AXIS", "model")

import argparse   # noqa: E402
import json       # noqa: E402
import re         # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402
from typing import Any, Dict  # noqa: E402

import jax                    # noqa: E402
import numpy as np            # noqa: E402

from repro.configs import SHAPES, get_config, list_archs  # noqa: E402
from repro.launch.mesh import make_production_mesh        # noqa: E402
from repro.launch import sharding as shd                  # noqa: E402
from repro.models import registry as reg                  # noqa: E402
from repro.models import transformer as tfm               # noqa: E402
from repro.train.loop import make_train_step              # noqa: E402
from repro.train.optimizer import AdamWConfig, init_state  # noqa: E402

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"(\w+)\[([\d,]*)\][^=]*\b"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-operand bytes of collective ops in the (SPMD, per-device)
    HLO module, bucketed by collective kind."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if ("all-reduce" not in line and "all-gather" not in line
                and "reduce-scatter" not in line and "all-to-all" not in line
                and "collective-permute" not in line):
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        out[kind] = out.get(kind, 0) + n * size
    return out


def build_lowerable(arch: str, shape_name: str, mesh, cfg=None):
    """Returns (fn, arg_shapes, in_shardings) ready for jit().lower()."""
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    window = reg.decode_window(cfg, shape)
    inputs = reg.input_specs(cfg, shape)
    params_shape = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    pspecs = shd.param_specs(cfg, params_shape, mesh)
    ispecs = shd.input_spec_tree(cfg, shape, mesh, inputs)

    if shape.kind == "train":
        opt = AdamWConfig(total_steps=1000)
        step = make_train_step(
            cfg, opt, remat=True,
            microbatches=int(os.environ.get("REPRO_MICROBATCH", "1")))
        opt_shape = jax.eval_shape(init_state, params_shape)
        mspecs = pspecs
        if os.environ.get("REPRO_ZERO1"):
            mspecs = shd.zero1_opt_specs(params_shape, pspecs, mesh)
        ospecs = type(opt_shape)(
            step=jax.sharding.PartitionSpec(),
            mu=mspecs, nu=jax.tree.map(lambda s: s, mspecs))
        fn = step
        args = (params_shape, opt_shape, inputs)
        shardings = (pspecs, ospecs, ispecs)
    elif shape.kind == "prefill":
        def fn(params, batch):
            return tfm.prefill(cfg, params, batch, shape.seq_len,
                               window=window)
        args = (params_shape, inputs)
        shardings = (pspecs, ispecs)
    else:
        def fn(params, tokens, cache, lengths):
            return tfm.decode_step(cfg, params, tokens, cache, lengths,
                                   window=window)
        args = (params_shape, inputs["tokens"], inputs["cache"],
                inputs["lengths"])
        shardings = (pspecs, ispecs["tokens"], ispecs["cache"],
                     ispecs["lengths"])
    return fn, args, shardings


def run_one(arch: str, shape_name: str, multi_pod: bool) -> Dict[str, Any]:
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
    }
    t0 = time.time()
    try:
        # looped scan: realistic buffer reuse for memory_analysis
        os.environ["REPRO_SCAN_UNROLL"] = "1"
        mesh = make_production_mesh(multi_pod=multi_pod)
        fn, args, shardings = build_lowerable(arch, shape_name, mesh)
        named = shd.to_named(shardings, mesh)
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=named)
            lowered = jitted.lower(*args)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)
        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)}
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        rec["flops"] = float(cost.get("flops", 0.0))
        rec["bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
        rec["transcendentals"] = float(cost.get("transcendentals", 0.0))
        rec["collectives"] = collective_bytes(compiled.as_text())
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def _probe_cfg(cfg, repeats: int):
    """Full-width config with `lead + repeats*period` layers (and a
    matching-depth encoder) — used for layer-linear cost extrapolation."""
    import dataclasses
    from repro.models.transformer import split_pattern
    lead, p, r = split_pattern(cfg)
    kw = {"num_layers": lead + repeats * p}
    if cfg.encoder is not None and cfg.encoder.num_layers > 0:
        # scale encoder depth with the same repeat count (whisper: 24/24)
        per = cfg.encoder.num_layers // r
        kw["encoder"] = dataclasses.replace(cfg.encoder,
                                            num_layers=per * repeats)
    return dataclasses.replace(cfg, **kw)


def _lower_costs(arch: str, shape_name: str, mesh, cfg) -> Dict[str, Any]:
    fn, args, shardings = build_lowerable(arch, shape_name, mesh, cfg=cfg)
    named = shd.to_named(shardings, mesh)
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=named).lower(*args).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collectives": collective_bytes(compiled.as_text()),
    }


def run_cost(arch: str, shape_name: str, multi_pod: bool) -> Dict[str, Any]:
    """Layer-linear cost model: probe with 1 and 2 repeats (unrolled scans),
    extrapolate to the full depth. Exact for periodic stacks; avoids both
    the while-loop undercount and full-depth unrolled compiles."""
    from repro.models.transformer import split_pattern
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "kind": "cost",
    }
    t0 = time.time()
    try:
        os.environ["REPRO_SCAN_UNROLL"] = "full"
        cfg = get_config(arch)
        lead, p, r = split_pattern(cfg)
        mesh = make_production_mesh(multi_pod=multi_pod)
        c1 = _lower_costs(arch, shape_name, mesh, _probe_cfg(cfg, 1))
        c2 = _lower_costs(arch, shape_name, mesh, _probe_cfg(cfg, 2))
        rec["probe_repeats"] = [1, 2]
        rec["full_repeats"] = r

        def extrap(a, b):
            return a + (r - 1) * (b - a)

        rec["flops"] = extrap(c1["flops"], c2["flops"])
        rec["bytes_accessed"] = extrap(c1["bytes_accessed"],
                                       c2["bytes_accessed"])
        kinds = set(c1["collectives"]) | set(c2["collectives"])
        rec["collectives"] = {
            k: int(max(0, extrap(c1["collectives"].get(k, 0),
                                 c2["collectives"].get(k, 0))))
            for k in kinds}
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--cost", action="store_true",
                    help="probe-extrapolated cost model instead of the "
                         "full-depth memory dry-run")
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args()

    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = (run_cost if args.cost else run_one)(arch, shape, mp)
                line = json.dumps(rec)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(line + "\n")
                short = {k: rec[k] for k in
                         ("arch", "shape", "mesh", "ok", "total_s")
                         if k in rec}
                if rec["ok"]:
                    short["flops"] = f"{rec['flops']:.3e}"
                    if "memory" in rec:
                        short["temp_gb"] = round(
                            rec["memory"].get("temp_size_in_bytes", 0)
                            / 2**30, 2)
                else:
                    short["error"] = rec.get("error", "")[:200]
                print(json.dumps(short), flush=True)


if __name__ == "__main__":
    main()
