import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we build abstract inputs (ShapeDtypeStruct only — zero
allocation), jit the step function with explicit in/out shardings over the
production mesh, ``.lower().compile()``, and extract:

  * ``compiled.cost_analysis()``   -> HLO FLOPs / bytes accessed,
  * ``compiled.memory_analysis()`` -> per-device buffer sizes (proves fit),
  * the partitioned HLO text       -> per-collective operand bytes
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute), which cost_analysis does not report,

and derive the three roofline terms (docs/EXPERIMENTS.md §Roofline) against
TPU v5e constants. One JSON artifact per cell; ``--sweep`` runs every cell in
a subprocess (resumable — existing artifacts are skipped).

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --arch qwen3-moe-30b-a3b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --sweep --out-dir artifacts/dryrun
"""
import argparse
import json
import re
import sys
import time
from pathlib import Path

# v5e per-chip hardware constants (roofline denominators)
PEAK_FLOPS = 197e12      # bf16 FLOP/s
HBM_BW = 819e9           # B/s
LINK_BW = 50e9           # B/s per ICI link

from repro.launch import hloparse


def ring_link_bytes(collectives: dict) -> float:
    """Per-device bytes crossing the busiest link, ring-algorithm model:
    all-gather / reduce-scatter move (g-1)/g of the full buffer; all-reduce
    2x that; permute moves the operand once."""
    total = 0.0
    for op, rec in collectives.items():
        gs = rec.get("group_sizes") or {}
        n = sum(gs.values())
        g = (sum(int(k) * v for k, v in gs.items()) / n) if n else 2.0
        frac = (g - 1.0) / g if g > 1 else 0.0
        if op == "all-gather":
            total += rec["result_bytes"] * frac
        elif op == "reduce-scatter":
            total += rec["operand_bytes"] * frac
        elif op == "all-reduce":
            total += 2.0 * rec["operand_bytes"] * frac
        elif op in ("all-to-all", "ragged-all-to-all"):
            total += rec["operand_bytes"] * frac
        elif op == "collective-permute":
            total += rec["operand_bytes"]
    return total


# ---------------------------------------------------------------------------
# Cell construction
# ---------------------------------------------------------------------------

def build_cell(arch: str, shape_name: str, multi_pod: bool, *,
               rules_mode=None, q_chunk=512, remat=True, rwkv_chunk=32,
               use_flash=True):
    """Returns (jitted_fn, abstract_args, meta). Imports jax lazily so the
    XLA_FLAGS line above always wins."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import SHAPE_BY_NAME, shape_applicable
    from repro.configs.registry import get_config
    from repro.launch import sharding as SH
    from repro.launch.mesh import make_production_mesh
    from repro.models import transformer as T
    from repro.optim import adamw
    from repro.train import step as S

    cfg = get_config(arch)
    shape = SHAPE_BY_NAME[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return None, None, {"skipped": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    mode = "train" if shape.kind == "train" else "serve"
    rules = SH.ShardingRules(mode=rules_mode or mode)
    shd = SH.make_sharder(mesh, rules)
    make_ctx = lambda: T.Ctx(mode="train", shd=shd, q_chunk=q_chunk,
                             remat=remat, rwkv_chunk=rwkv_chunk,
                             flash=use_flash)

    from repro.models.params import abstract_params

    specs = T.param_specs(cfg)
    aparams = abstract_params(specs)
    psh = SH.tree_param_shardings(specs, mesh, rules)
    repl = SH.replicated(mesh)

    B, Sq = shape.global_batch, shape.seq_len
    meta = {
        "arch": arch, "config": cfg.name, "shape": shape_name,
        "mesh": list(mesh.devices.shape), "axes": list(mesh.axis_names),
        "chips": chips, "kind": shape.kind,
        "n_params": cfg.n_params, "n_active_params": cfg.n_active_params,
    }

    if shape.kind == "train":
        opt_cfg = adamw.AdamWConfig()
        aopt = adamw.abstract_state(aparams, opt_cfg)
        ospecs = _opt_specs(specs, opt_cfg)
        osh = {"m": SH.tree_param_shardings(ospecs["m"], mesh, rules),
               "v": SH.tree_param_shardings(ospecs["v"], mesh, rules),
               "step": repl}
        abatch = S.abstract_batch(cfg, B, Sq)
        bsh = SH.batch_shardings(abatch, mesh, rules)
        fn = S.make_train_step(cfg, opt_cfg, make_ctx)
        msh = {k: repl for k in ("loss", "ce", "moe_aux", "grad_norm")}
        jf = jax.jit(fn, in_shardings=(psh, osh, bsh),
                     out_shardings=(psh, osh, msh), donate_argnums=(0, 1))
        args = (aparams, aopt, abatch)
    elif shape.kind == "prefill":
        acache = T.abstract_cache(cfg, B, Sq)
        csh = SH.tree_param_shardings(T.cache_specs(cfg, B, Sq), mesh, rules)
        abatch = S.abstract_batch(cfg, B, Sq)
        bsh = SH.batch_shardings(abatch, mesh, rules)
        fn = S.make_prefill_step(cfg, lambda: T.Ctx(
            mode="prefill", shd=shd, q_chunk=q_chunk, remat=remat,
            rwkv_chunk=rwkv_chunk, flash=use_flash))
        lsh = NamedSharding(mesh, SH.resolve((B, 1, cfg.vocab),
                                             ("batch", None, "vocab"),
                                             mesh, rules, "act"))
        jf = jax.jit(fn, in_shardings=(psh, bsh, csh),
                     out_shardings=(lsh, csh), donate_argnums=(2,))
        args = (aparams, abatch, acache)
    else:  # decode
        acache = T.abstract_cache(cfg, B, Sq)
        csh = SH.tree_param_shardings(T.cache_specs(cfg, B, Sq), mesh, rules)
        atok = jax.ShapeDtypeStruct((B,), jnp.int32)
        apos = jax.ShapeDtypeStruct((), jnp.int32)
        fn = S.make_decode_step(cfg, lambda: T.Ctx(mode="decode", shd=shd,
                                                   q_chunk=q_chunk, remat=False))
        toksh = NamedSharding(mesh, SH.resolve((B,), ("batch",), mesh, rules, "act"))
        lsh = NamedSharding(mesh, SH.resolve((B, 1, cfg.vocab),
                                             ("batch", None, "vocab"),
                                             mesh, rules, "act"))
        jf = jax.jit(fn, in_shardings=(psh, toksh, csh, repl),
                     out_shardings=(lsh, csh), donate_argnums=(2,))
        args = (aparams, atok, acache, apos)
    return jf, args, meta


def _opt_specs(specs, opt_cfg):
    """ParamSpec tree for optimizer moments (fp32 mirror of params)."""
    import dataclasses as dc

    import jax

    from repro.models.params import is_spec

    def mom(s):
        return dc.replace(s, dtype=opt_cfg.moment_dtype, init="zeros")

    m = jax.tree_util.tree_map(mom, specs, is_leaf=is_spec)
    return {"m": m, "v": m}


# ---------------------------------------------------------------------------
# Roofline terms
# ---------------------------------------------------------------------------

def model_flops(meta, shape_kind: str, tokens: int) -> float:
    n = meta["n_active_params"]
    if shape_kind == "train":
        return 6.0 * n * tokens
    return 2.0 * n * tokens


def roofline(meta, parsed: "hloparse.Costs", chips: int, tokens: int) -> dict:
    """Three-term roofline from the trip-count-scaled per-device HLO costs."""
    flops_dev = parsed.flops
    bytes_dev = parsed.hbm_bytes
    coll_operand_dev = float(sum(v["operand_bytes"]
                                 for v in parsed.collectives.values()))
    link_dev = ring_link_bytes(parsed.collectives)
    terms = {
        "compute_s": flops_dev / PEAK_FLOPS,
        "memory_s": bytes_dev / HBM_BW,
        "collective_s": link_dev / LINK_BW,            # ring model (used)
        "collective_s_spec": coll_operand_dev / LINK_BW,  # literal spec formula
        "hlo_flops_per_dev": flops_dev,
        "hlo_bytes_per_dev": bytes_dev,
        "collective_link_bytes_per_dev": link_dev,
        "collective_operand_bytes_per_dev": coll_operand_dev,
    }
    dom = max(("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])
    terms["bottleneck"] = dom
    mf = model_flops(meta, meta["kind"], tokens)
    terms["model_flops"] = mf
    hlo_global = flops_dev * chips
    terms["useful_flop_ratio"] = (mf / hlo_global) if hlo_global else 0.0
    terms["roofline_fraction"] = (
        (mf / chips / PEAK_FLOPS) / max(terms[dom], 1e-30))
    return terms


# ---------------------------------------------------------------------------
# Single-cell run
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool, out_path=None,
             save_hlo=False, **build_kw) -> dict:
    from repro.configs.base import SHAPE_BY_NAME
    t0 = time.time()
    jf, args, meta = build_cell(arch, shape_name, multi_pod, **build_kw)
    rec = dict(meta)
    rec["multi_pod"] = multi_pod
    if jf is None:
        rec["status"] = "skipped"
        _write(rec, out_path)
        return rec
    shape = SHAPE_BY_NAME[shape_name]
    tokens = shape.tokens if shape.kind != "decode" else shape.global_batch
    try:
        lowered = jf.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)
        cost = compiled.cost_analysis() or {}
        if isinstance(cost, (list, tuple)):     # older jax: one dict per device
            cost = cost[0] if cost else {}
        try:
            mem = compiled.memory_analysis()
            rec["memory_analysis"] = {
                k: int(getattr(mem, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes",
                          "alias_size_in_bytes")
                if hasattr(mem, k)
            }
        except Exception as e:                       # pragma: no cover
            rec["memory_analysis_error"] = str(e)
        hlo = compiled.as_text()
        parsed = hloparse.analyze(hlo)
        rec["collectives"] = parsed.collectives
        rec["cost_analysis_raw"] = {           # note: counts loop bodies once
            k: v for k, v in cost.items()
            if k in ("flops", "bytes accessed", "transcendentals")}
        rec["roofline"] = roofline(meta, parsed, meta["chips"], tokens)
        rec["tokens"] = tokens
        rec["status"] = "ok"
        if save_hlo and out_path:
            Path(str(out_path).replace(".json", ".hlo.txt")).write_text(hlo)
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
    rec["total_s"] = round(time.time() - t0, 2)
    _write(rec, out_path)
    return rec


def _write(rec, out_path):
    if out_path:
        Path(out_path).parent.mkdir(parents=True, exist_ok=True)
        Path(out_path).write_text(json.dumps(rec, indent=1, default=str))


def list_cells():
    from repro.configs.base import SHAPES, shape_applicable
    from repro.configs.registry import ARCH_IDS, get_config
    cells = []
    for a in ARCH_IDS:
        cfg = get_config(a)
        for s in SHAPES:
            ok, why = shape_applicable(cfg, s)
            cells.append((a, s.name, ok, why))
    return cells


def sweep(out_dir: str, multi_pod_also=True, timeout=2400):
    import subprocess
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    jobs = []
    for mp in ([False, True] if multi_pod_also else [False]):
        for a, sname, ok, why in list_cells():
            tag = f"{a}__{sname}__{'mp' if mp else 'sp'}"
            jobs.append((a, sname, mp, out / f"{tag}.json"))
    for a, sname, mp, path in jobs:
        if path.exists():
            st = json.loads(path.read_text()).get("status")
            if st in ("ok", "skipped"):
                continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", a,
               "--shape", sname, "--out", str(path)]
        if mp:
            cmd.append("--multi-pod")
        print(f"[sweep] {path.stem}", flush=True)
        try:
            # the dry run compiles for forced CPU devices: the child never
            # needs (or contends for) an accelerator this process may hold
            subprocess.run(cmd, timeout=timeout, check=False,
                           env={**os.environ, "JAX_PLATFORMS": "cpu"})
        except subprocess.TimeoutExpired:
            _write({"arch": a, "shape": sname, "multi_pod": mp,
                    "status": "timeout", "timeout_s": timeout}, path)
    print("[sweep] done", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--out-dir", default="artifacts/dryrun")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()
    if args.list:
        for a, s, ok, why in list_cells():
            print(f"{a:26s} {s:12s} {'run' if ok else 'SKIP: ' + why}")
        return
    if args.sweep:
        sweep(args.out_dir, multi_pod_also=not args.single_pod_only)
        return
    rec = run_cell(args.arch, args.shape, args.multi_pod, args.out,
                   save_hlo=args.save_hlo)
    print(json.dumps(rec, indent=1, default=str))


if __name__ == "__main__":
    main()
