"""Multi-pod dry-run: lower + compile every (architecture x input-shape) on
the production meshes, record memory/cost/collective analyses.

A CPU-placeholder tool: 512 host-platform devices stand in for the
production meshes.  ``main`` pins this process and every per-cell child to
the CPU platform before JAX initializes a backend, so the dry-run never
contends for an accelerator; smoke tests and benches see 1 device.

Usage:
  python -m repro.launch.dryrun --arch minitron-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--jobs 4]

Each cell writes results/dryrun/<arch>_<shape>_<mesh>.json with:
  memory_analysis (per-device bytes), cost_analysis (per-device FLOPs/bytes),
  per-collective-type payload bytes parsed from the post-SPMD HLO.
"""
import argparse
import json
import os
import re
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_shape, list_archs
from repro.launch.mesh import make_production_mesh
from repro.models import build_model, cache_specs, input_specs, param_specs
from repro.models.common import activation_sharding
from repro.models.prefill import prefill
from repro.parallel import sharding as shd
from repro.serve.step import make_prefill_step, make_serve_step
from repro.train.optimizer import OptimizerConfig, OptState, make_optimizer
from repro.train.step import (
    make_pipeline_train_step, make_train_step, pipeline_state_shardings,
)

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s*(\(?[a-z0-9\[\],{}:#\s/_.*-]*?\)?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred|"
                       r"f8e4m3fn|f8e5m2|c64|c128)\[([0-9,]*)\]")


def collective_bytes(hlo: str) -> Dict[str, float]:
    """Sum per-device output payload bytes of each collective type from
    post-partitioning HLO text (async -start counted once, -done skipped)."""
    out: Dict[str, float] = {}
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done" in line.split("=")[-1][:60]:
            continue
        lhs, kind = m.group(1), m.group(2)
        total = 0.0
        for dt, dims in _SHAPE_RE.findall(lhs):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0.0) + total
    return out


def _opt_shardings(mesh, pspecs_tree):
    to_ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t)
    return OptState(NamedSharding(mesh, P()), to_ns(pspecs_tree),
                    to_ns(pspecs_tree))


N_MICROBATCHES = int(os.environ.get("REPRO_NMB", "16"))
# perf-iteration knobs (hillclimb; see EXPERIMENTS.md §Perf)
ZERO1 = os.environ.get("REPRO_ZERO1", "1") == "1"      # shard opt states
FSDP_PARAMS = os.environ.get("REPRO_FSDP", "1") == "1"  # shard params over data
ACT_BF16 = os.environ.get("REPRO_ACT_BF16", "0") == "1"  # bf16 compute
FLAT_DP = os.environ.get("REPRO_FLATDP", "0") == "1"    # batch over both axes
MASTER_W = os.environ.get("REPRO_MASTER", "0") == "1"   # bf16 params + f32 master


def build_case(arch: str, shape_name: str, multi_pod: bool,
               n_microbatches: int = N_MICROBATCHES,
               global_batch_override: int = 0):
    """Returns (fn, args, in_shardings, mesh) ready for jit().lower()."""
    import dataclasses
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    if global_batch_override:
        shape = dataclasses.replace(shape, global_batch=global_batch_override)
    mesh = make_production_mesh(multi_pod=multi_pod)
    opt_cfg = OptimizerConfig(state_dtype=jnp.bfloat16,
                              master_weights=MASTER_W)
    batch_structs = input_specs(cfg, shape)

    if shape.kind == "train":
        if multi_pod and cfg.family != "audio":
            # the paper's design: pipeline over the slow pod axis.
            # act_dtype=f32 works around an XLA *CPU* compiler abort
            # (AllReducePromotion aborts cloning a bf16 all-reduce produced
            # by the pipeline backward); the TPU compiler accepts bf16, the
            # target act dtype — pipeline activation-memory numbers here are
            # 2x the target.
            train_step, staging, opt_init, sh = make_pipeline_train_step(
                cfg, opt_cfg, mesh=mesh, n_stages=2,
                n_microbatches=n_microbatches, abstract=True,
                act_dtype=jnp.float32)
            ptree = {"staged": staging.staged, "shared": staging.shared}
            opt_struct = jax.eval_shape(opt_init, ptree)
            batch_sh = jax.tree.map(
                lambda x: NamedSharding(mesh, P("data", *([None] * (len(x.shape) - 1)))),
                batch_structs)
            # donate params/opt (in-place update semantics)
            args = (staging.staged, staging.shared, staging.consts,
                    opt_struct, batch_structs)
            staged_sh, shared_sh, opt_sh = pipeline_state_shardings(
                mesh, sh, staging, opt_struct)
            in_sh = (staged_sh, shared_sh, sh["consts"], opt_sh, batch_sh)
            return train_step, args, in_sh, mesh, (0, 1, 3)
        # single-pod (or multi-pod DP for sub-1B audio): DP/FSDP + TP
        rules = shd.train_act_rules()
        if multi_pod:
            rules = dict(rules, batch=("pod", "data"), expert=("pod", "data"))
        if FLAT_DP:
            # pure data parallelism over the whole pod for the transformer
            # stack (no TP all-reduces); the LM head keeps vocab over
            # 'model' with its batch over 'data' so CE logits stay sharded
            rules = dict(rules, batch=(("pod", "data", "model") if multi_pod
                                       else ("data", "model")),
                         batch_head=("pod", "data") if multi_pod else "data",
                         heads=None, kv_heads=None, ff=None, vocab="model")
        if ACT_BF16:
            from repro.models.common import set_act_dtype
            set_act_dtype(jnp.bfloat16)
        pdtype = jnp.bfloat16 if MASTER_W else jnp.float32
        train_step, model, opt_init = make_train_step(
            cfg, opt_cfg, act_rules=rules, n_microbatches=n_microbatches,
            param_dtype=pdtype)
        pspecs = shd.param_pspecs(param_specs(cfg, param_dtype=pdtype))
        opt_pspecs = pspecs
        if FLAT_DP:
            # flat DP: no TP sharding of weights. FSDP on -> shard dim0 over
            # BOTH axes (FSDP-256, bf16 gathers); FSDP off -> fully replicate
            # (small models). ZeRO: opt states sharded over the axis pair.
            if FSDP_PARAMS:
                pspecs = jax.tree.map(
                    lambda sp: P(("data", "model"), *([None] * (len(sp) - 1)))
                    if len(sp) else sp, pspecs)
            else:
                pspecs = jax.tree.map(lambda sp: P(*([None] * len(sp))), pspecs)
            opt_pspecs = jax.tree.map(
                lambda sp: P(("data", "model"), *([None] * (len(sp) - 1)))
                if len(sp) else sp, opt_pspecs)
        elif not FSDP_PARAMS:
            # ZeRO-1 layout: params replicated over data (no per-microbatch
            # all-gather); optimizer states stay sharded over data
            pspecs = jax.tree.map(
                lambda sp: P(*[None if e == "data" else e for e in sp]),
                pspecs)
            opt_pspecs = jax.tree.map(
                lambda sp: P(*[("data" if e is None else e) if i == 0 else e
                               for i, e in enumerate(sp)]) if len(sp) else sp,
                opt_pspecs)
        pshard = shd.fitted_shardings(mesh, pspecs,
                                      param_specs(cfg, param_dtype=pdtype))
        opt_struct = jax.eval_shape(opt_init, param_specs(cfg, param_dtype=pdtype))
        if FLAT_DP:
            batch_axis = (("pod", "data", "model") if multi_pod
                          else ("data", "model"))
        else:
            batch_axis = ("pod", "data") if multi_pod else "data"
        batch_sh = jax.tree.map(
            lambda x: NamedSharding(mesh, P(batch_axis, *([None] * (len(x.shape) - 1)))),
            batch_structs)
        args = (param_specs(cfg, param_dtype=pdtype), opt_struct, batch_structs)
        opt_sh = OptState(
            NamedSharding(mesh, P()),
            shd.fitted_shardings(mesh, opt_pspecs, opt_struct.mu),
            shd.fitted_shardings(mesh, opt_pspecs, opt_struct.nu),
            (shd.fitted_shardings(mesh, opt_pspecs, opt_struct.master)
             if opt_struct.master is not None else None))
        in_sh = (pshard, opt_sh, batch_sh)
        return train_step, args, in_sh, mesh, (0, 1)

    pspecs = shd.param_pspecs(param_specs(cfg))
    pshard = shd.fitted_shardings(mesh, pspecs, param_specs(cfg))

    if shape.kind == "prefill":
        rules = shd.prefill_act_rules(multi_pod=multi_pod)

        def prefill_step(params, batch):
            with activation_sharding(rules):
                return prefill(cfg, params, batch)

        batch_axis = rules["batch"]
        batch_sh = jax.tree.map(
            lambda x: NamedSharding(mesh, P(batch_axis, *([None] * (len(x.shape) - 1)))),
            batch_structs)
        args = (param_specs(cfg), batch_structs)
        return prefill_step, args, (pshard, batch_sh), mesh, ()

    # decode
    serve_step, model, rules = make_serve_step(cfg, shape=shape,
                                               multi_pod=multi_pod)
    cache_structs = cache_specs(cfg, shape)
    cache_sh = shd.fitted_shardings(
        mesh, shd.cache_pspecs(cache_structs, rules), cache_structs)
    tok_axis = rules["batch"]
    tok_sh = NamedSharding(mesh, P(tok_axis, None))
    args = (param_specs(cfg), cache_structs,
            jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))
    in_sh = (pshard, cache_sh, tok_sh, NamedSharding(mesh, P()))
    return serve_step, args, in_sh, mesh, (1,)  # donate the cache


def _analyze(compiled) -> Dict[str, Any]:
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    colls = collective_bytes(hlo)
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "colls": colls, "hlo_chars": len(hlo)}


def _combine(base: Dict[str, Any], per_mb: Dict[str, Any], n_units: float
             ) -> Dict[str, Any]:
    """total = f(1) + (n_units - 1) * (f(2) - f(1)) per linear decomposition."""
    out = {}
    for key in ("flops", "bytes"):
        out[key] = base[key] + (n_units - 1) * max(per_mb[key] - base[key], 0.0)
    kinds = set(base["colls"]) | set(per_mb["colls"])
    out["colls"] = {
        k: base["colls"].get(k, 0.0) + (n_units - 1)
        * max(per_mb["colls"].get(k, 0.0) - base["colls"].get(k, 0.0), 0.0)
        for k in kinds}
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             out_dir: str = "results/dryrun",
             skip_analysis: bool = False) -> Dict[str, Any]:
    from repro.models import common as mcommon
    multi_pod = mesh_kind == "multi"
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_kind, "ok": False}
    t0 = time.time()
    try:
        # --- pass 1: production (scanned) — the compile proof + memory ------
        fn, args, in_sh, mesh, donate = build_case(arch, shape_name, multi_pod)
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=in_sh,
                              donate_argnums=donate).lower(*args)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_per_device": ma.argument_size_in_bytes
            + ma.output_size_in_bytes + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        }
        raw = _analyze(compiled)
        rec["scanned_flops_per_device"] = raw["flops"]
        rec["ok"] = True

        # --- pass 2: analysis (unrolled) — exact FLOPs/collectives ----------
        # XLA counts while bodies once; unrolling + a linear (n_mb=1, n_mb=2)
        # decomposition recovers exact per-step totals (see models/common.py).
        if not skip_analysis:
            mcommon.set_unroll(True)
            try:
                if shape.kind == "train":
                    mb_seqs = max(1, shape.global_batch // N_MICROBATCHES)
                    a1 = _cell_analysis(arch, shape_name, multi_pod, 1, mb_seqs)
                    if os.environ.get("REPRO_FAST_ANALYSIS") == "1":
                        # single-pass: scale everything by n_mb (overcounts
                        # the once-per-step optimizer collectives ~params
                        # bytes x (n_mb-1); documented in EXPERIMENTS.md)
                        tot = {"flops": a1["flops"] * N_MICROBATCHES,
                               "bytes": a1["bytes"] * N_MICROBATCHES,
                               "colls": {k: v * N_MICROBATCHES
                                         for k, v in a1["colls"].items()}}
                        rec["analysis_mode"] = "scaled-1pass"
                    else:
                        a2 = _cell_analysis(arch, shape_name, multi_pod, 2,
                                            2 * mb_seqs)
                        # pipeline slots = n_mb+S-1; grad-accum units = n_mb
                        tot = _combine(a1, a2, N_MICROBATCHES)
                else:
                    tot = _cell_analysis(arch, shape_name, multi_pod, 1, 0)
                rec["flops_per_device"] = tot["flops"]
                rec["bytes_per_device"] = tot["bytes"]
                rec["collectives"] = tot["colls"]
                rec["collective_bytes_per_device"] = float(
                    sum(tot["colls"].values()))
            finally:
                mcommon.set_unroll(False)
    except Exception as e:  # noqa
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        rec["ok"] = False
    rec["total_s"] = round(time.time() - t0, 2)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{arch}_{shape_name}_{mesh_kind}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
    return rec


def _cell_analysis(arch, shape_name, multi_pod, n_mb, global_batch):
    fn, args, in_sh, mesh, donate = build_case(
        arch, shape_name, multi_pod, n_microbatches=n_mb,
        global_batch_override=global_batch)
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=in_sh,
                           donate_argnums=donate).lower(*args).compile()
    return _analyze(compiled)


def all_cells(mesh_kinds=("single", "multi")) -> List[Tuple[str, str, str]]:
    cells = []
    for arch in list_archs(assigned_only=True):
        cfg = get_config(arch)
        for shape in cfg.shapes():
            for mk in mesh_kinds:
                cells.append((arch, shape.name, mk))
    return cells


def use_cpu_placeholders() -> None:
    """Pin this process, and the children that inherit its environment, to
    512 CPU placeholder devices.  Must run before JAX initializes a backend
    (the device count and the platform are fixed then)."""
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


def main() -> None:
    use_cpu_placeholders()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--skip-analysis", action="store_true",
                    help="production compile + memory only (no unrolled "
                         "cost passes) — used for multi-pod cells whose "
                         "roofline is out of scope")
    args = ap.parse_args()

    if not args.all:
        kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        for mk in kinds:
            rec = run_cell(args.arch, args.shape, mk, args.out,
                           skip_analysis=args.skip_analysis)
            status = "OK" if rec["ok"] else f"FAIL: {rec.get('error')}"
            print(f"[{args.arch} x {args.shape} x {mk}] {status} "
                  f"({rec['total_s']}s)")
            if rec.get("ok"):
                print(f"  peak/device: {rec['memory']['peak_per_device']/2**30:.2f} GiB, "
                      f"flops/device: {rec['flops_per_device']:.3e}, "
                      f"collective B/device: {rec['collective_bytes_per_device']:.3e}")
        return

    # orchestrate: one subprocess per cell (isolated compile memory)
    kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = all_cells(tuple(kinds))
    if not args.force:
        cells = [c for c in cells if not os.path.exists(
            os.path.join(args.out, f"{c[0]}_{c[1]}_{c[2]}.json"))]
    print(f"{len(cells)} cells to run, {args.jobs} parallel jobs", flush=True)
    procs: List[Tuple[subprocess.Popen, Tuple, int]] = []
    pending = [(c, 0) for c in cells]
    failures = []
    MAX_RETRY = 2  # XLA CPU occasionally F-crashes under concurrent compiles
    while pending or procs:
        while pending and len(procs) < args.jobs:
            cell, tries = pending.pop(0)
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", cell[0], "--shape", cell[1], "--mesh", cell[2],
                   "--out", args.out]
            if args.skip_analysis or cell[2] == "multi":
                # roofline is single-pod scope; multi-pod cells only need
                # the compile proof + memory analysis
                cmd.append("--skip-analysis")
            procs.append((subprocess.Popen(
                cmd, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL),
                cell, tries))
        time.sleep(2)
        still = []
        for p, cell, tries in procs:
            if p.poll() is None:
                still.append((p, cell, tries))
                continue
            path = os.path.join(args.out, f"{cell[0]}_{cell[1]}_{cell[2]}.json")
            ok, crashed = False, p.returncode != 0
            if os.path.exists(path):
                with open(path) as f:
                    ok = json.load(f).get("ok", False)
            if not ok and (crashed or not os.path.exists(path)) \
                    and tries < MAX_RETRY:
                print(f"  retry {cell} (exit {p.returncode})", flush=True)
                pending.append((cell, tries + 1))
            elif not ok:
                failures.append(cell)
                print(f"  done {cell} -> FAIL", flush=True)
            else:
                print(f"  done {cell} -> OK", flush=True)
        procs = still
    print(f"all cells done, failures={failures}", flush=True)


if __name__ == "__main__":
    main()
