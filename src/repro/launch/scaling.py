"""Multi-APU scaling driver: one decomposed cavity replay per node size.

Runs the `fig_scaling` measurement for ONE simulated node size: capture a
SIMPLE time-step, replay it on a single device and domain-decomposed
across ``--apus`` simulated APUs (``repro.core.shard_program``), assert
numerical parity (docs/DESIGN.md §2 tolerance), and report the node-level
compute / staging / inter-APU-exchange / overlap split from the
aggregated per-device ledgers.

The exchange schedule is selectable (docs/SCALING.md): ``--schedule
overlap`` (default) hides halo exchanges behind interior compute,
``sequential`` is the exposed PR-3 baseline, ``split`` runs the causal
interior/boundary sub-region split.  ``--halo-multiplier k`` exchanges
``k``-wide ghosts every ``k``-th stencil application, and ``--mesh 2x2``
decomposes over a 2-D mesh to cut surface-to-volume.  Grid extents that
don't divide over the mesh are padded up to the next multiple
(remainder-row padding — both replays run the padded grid, so parity
stays meaningful).

On forced CPU devices each invocation must own its process: the APU
count is baked into ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
*before* the first jax import (the ``launch.dryrun`` trick). On real chips
the flag changes nothing, and a child process could not reach chips its
parent holds. :func:`run` makes that choice for callers that have already
imported jax (``benchmarks/run.py fig_scaling``, the tuner's
``cfd_sharded``):

  PYTHONPATH=src python -m repro.launch.scaling --apus 4 --mesh 2x2 \\
      --steps 2 --grid 16,16,16 --policy unified \\
      --out artifacts/scaling/apu4.json
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--apus", type=int, default=2,
                    help="simulated APUs (forced host-platform devices)")
    ap.add_argument("--mesh", default="",
                    help="mesh shape over the APUs, e.g. '4' (1-D) or "
                         "'2x2' (2-D, cuts surface-to-volume); default: "
                         "1-D over --apus")
    ap.add_argument("--steps", type=int, default=2,
                    help="replayed time-steps per measurement")
    ap.add_argument("--grid", default="8,8,8",
                    help="cavity grid; extents that don't divide over the "
                         "mesh are padded up to the next multiple")
    ap.add_argument("--policy", default="unified",
                    choices=("unified", "discrete", "host", "adaptive",
                             "auto"),
                    help="'auto' loads the tuned cfd_sharded profile "
                         "entry for this grid (repro.tune) and, where "
                         "--mesh/--schedule/--halo-multiplier are left "
                         "at their defaults, adopts the winner's values")
    ap.add_argument("--variant", default="ref",
                    help="implementation variant both replays run under "
                         "(StaticSelector; regions without it fall back "
                         "to ref — docs/VARIANTS.md)")
    ap.add_argument("--schedule", default="overlap",
                    choices=("overlap", "sequential", "split"),
                    help="halo-exchange schedule (docs/SCALING.md)")
    ap.add_argument("--halo-multiplier", type=int, default=1,
                    help="wide-halo ghost depth: exchange k-wide ghosts "
                         "every k-th stencil application")
    ap.add_argument("--inner-max", type=int, default=6)
    ap.add_argument("--out", default="", help="also write the JSON here")
    return ap.parse_args(argv)


def pad_grid(grid, mesh_shape, shard_dims=None):
    """Remainder-row padding: grow each decomposed grid extent to the next
    multiple of its mesh-axis size so every APU holds an equal chunk
    (odd-sized production grids must not silently replicate).  Mesh axes
    map to the trailing grid dimensions (the ShardExecutor default)."""
    dims = shard_dims or range(-len(mesh_shape), 0)
    grid = list(grid)
    for dim, n in zip(dims, mesh_shape):
        e = grid[dim]
        grid[dim] = -(-e // n) * n
    return tuple(grid)


def main(argv=None) -> dict:
    args = parse_args(argv)
    if "jax" not in sys.modules:
        # mesh.apu_flags spells the same flag, but importing repro.launch
        # .mesh would itself import jax — too late to set flags after that.
        # Ours goes LAST: with repeated absl flags the last occurrence
        # wins, so an inherited device-count pin cannot override the run.
        flag = f"--xla_force_host_platform_device_count={args.apus}"
        os.environ["XLA_FLAGS"] = " ".join(
            [os.environ.get("XLA_FLAGS", ""), flag]).strip()
    import jax
    import numpy as np

    if jax.device_count() < args.apus:
        raise SystemExit(
            f"jax sees {jax.device_count()} device(s) but --apus="
            f"{args.apus}; run this module in a fresh process (it sets "
            "XLA_FLAGS itself) or export XLA_FLAGS first")

    from repro.cfd.grid import Grid
    from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
    from repro.core.regions import Executor, StaticSelector, make_policy
    from repro.core.shard_program import shard_program
    from repro.launch.compilation import configure_compilation
    from repro.launch.mesh import make_apu_mesh, parse_mesh_shape

    configure_compilation()

    tuned_cell = None
    if args.policy == "auto":
        # tuned warm-start: nearest cfd_sharded profile cell for this
        # grid; CLI knobs left at their defaults adopt the winner's
        # values, explicit non-default flags win (imported after the jax
        # flag dance above — repro.tune's harness imports model code)
        from repro.launch.policy import auto_policy
        from repro.tune.space import cfd_size
        grid_req = tuple(int(g) for g in args.grid.split(","))
        pol = auto_policy("cfd_sharded", cfd_size(grid_req))
        tuned = getattr(pol, "tuned_entry", None)
        args.policy = (tuned.candidate.placement if tuned is not None
                       else "unified")
        if tuned is not None:
            tuned_cell = tuned.key
            c = tuned.candidate
            if not args.mesh and c.mesh and len(c.mesh) > 1:
                prod = 1
                for m in c.mesh:
                    prod *= m
                if prod == args.apus:
                    args.mesh = "x".join(str(m) for m in c.mesh)
            if args.schedule == "overlap":
                args.schedule = c.schedule
            if args.halo_multiplier == 1:
                args.halo_multiplier = c.halo_multiplier

    mesh_shape = parse_mesh_shape(args.mesh) if args.mesh else (args.apus,)
    n_mesh = 1
    for s in mesh_shape:
        n_mesh *= s
    if n_mesh != args.apus:
        raise SystemExit(f"mesh {mesh_shape} needs {n_mesh} APUs but "
                         f"--apus={args.apus}")
    grid_requested = tuple(int(g) for g in args.grid.split(","))
    grid = pad_grid(grid_requested, mesh_shape)
    cfg = SimpleConfig(grid=Grid(grid), nu=0.1, inner_max=args.inner_max)
    app = SimpleFoam(cfg)
    st = init_state(cfg)
    st, _, _ = app.run_steps(st, 1)          # develop flow + warm caches
    prog = app.capture_step(st)

    # BOTH replays run the same variant selection, so sharded-vs-single
    # parity stays within the §2 bound whichever implementation runs
    selector = StaticSelector(args.variant)

    # single-device reference replay of the same trace
    ref_policy = make_policy(args.policy)
    ref_policy.selector = selector
    ref = Executor(ref_policy)
    app.replay_steps(prog, st, 1, ref)       # warm per-sharding compiles
    ref.ledger.reset_timings()
    s_ref, fom_ref = app.replay_steps(prog, st, args.steps, ref)

    # decomposed replay across the simulated node
    mesh = make_apu_mesh(mesh_shape)
    sh_policy = make_policy(args.policy)
    sh_policy.selector = selector
    sp = shard_program(prog, mesh, sh_policy,
                       halo_multiplier=args.halo_multiplier,
                       overlap=args.schedule != "sequential",
                       split_stencil=args.schedule == "split")
    app.replay_steps(prog, st, 1, sp)        # warm sharded compiles
    sp.reset_timings()
    s_sh, fom_sh = app.replay_steps(prog, st, args.steps, sp)

    fields = zip((s_ref.u, s_ref.v, s_ref.w, s_ref.p),
                 (s_sh.u, s_sh.v, s_sh.w, s_sh.p))
    max_err = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                  for a, b in fields)
    scale = max(float(np.max(np.abs(np.asarray(f))))
                for f in (s_ref.u, s_ref.v, s_ref.w, s_ref.p))
    # docs/DESIGN.md §2: float32 replay parity tolerance
    tol = 1e-5 * max(scale, 1.0)
    rep = sp.coverage_report()
    rec = {
        "apus": args.apus,
        "mesh_shape": list(mesh_shape),
        "grid": list(grid),
        "grid_requested": list(grid_requested),
        "grid_padded": grid != grid_requested,
        "steps": args.steps,
        "policy": args.policy,
        "tuned_cell": tuned_cell,
        "variant": args.variant,
        "schedule": args.schedule,
        "halo_multiplier": args.halo_multiplier,
        "impl_counts": rep["impl_counts"],
        "ops": len(prog),
        "fom_single_s": fom_ref,
        "fom_sharded_s": fom_sh,
        "exchange_fraction": rep["exchange_fraction"],
        "exchange_s": rep["exchange_s"],
        "overlap_s": rep["overlap_s"],
        "parity_max_abs_err": max_err,
        "parity_tol": tol,
        "parity_ok": bool(max_err <= tol),
        # where the decomposed replay's fields live: how many devices hold
        # a shard, and the shape of one shard
        "field_devices": len(s_sh.u.devices()),
        "field_shard_shape": list(s_sh.u.addressable_shards[0].data.shape),
        "halo_rows": sorted(n for n in sp.ledgers[0].regions
                            if n.startswith("halo(")),
        "report": rep,
    }
    if not rec["parity_ok"]:
        rec["status"] = "parity_failure"
    else:
        rec["status"] = "ok"
    if args.out:
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(rec, indent=1, default=str))
    print(json.dumps({k: v for k, v in rec.items() if k != "report"},
                     indent=1, default=str))
    if not rec["parity_ok"]:
        raise SystemExit(2)
    return rec


def run(argv, out) -> dict:
    """One scaling measurement, written to ``out`` and returned, from a
    process that has imported jax: in this process when the devices are
    accelerator chips, in a child process when they are forced CPU devices
    (the child sets the device count before its own jax import). Raises
    RuntimeError when the measurement fails."""
    import jax
    argv = [*argv, "--out", str(out)]
    if jax.devices()[0].platform != "cpu":
        try:
            return main(argv)
        except SystemExit as e:
            raise RuntimeError(f"scaling run exited with {e.code}") from e
    r = subprocess.run([sys.executable, "-m", "repro.launch.scaling", *argv],
                       capture_output=True, text=True)
    if r.returncode != 0:
        raise RuntimeError(f"scaling subprocess rc={r.returncode}:\n"
                           f"{r.stderr[-2000:]}")
    return json.loads(Path(out).read_text())


if __name__ == "__main__":
    main()
