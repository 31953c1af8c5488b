#!/usr/bin/env python3
"""Smoke run of the main paths on TPU chips, through the normal entry points.

    python chip_smoke.py              # one chip: CFD, host routing, serving
    python chip_smoke.py --chips 4    # four chips: decomposed CFD replay only

One chip:

1. device check: prints platform, device kind and count; exits non-zero
   unless JAX finds a TPU;
2. CFD: the SIMPLE step at 128^3 (2.1M cells). ``SimpleFoam.run_steps``,
   ``capture_step``, then ``replay_steps`` under ``UnifiedPolicy`` with the
   ``ref`` and the ``pallas`` variants and under ``DiscretePolicy``; every
   replay must match the ref replay within docs/DESIGN.md §2's
   ``1e-5 * max(scale, 1)``, the kernel-backed regions must have run
   ``pallas``, and the discrete replay's results must sit in
   ``pinned_host``;
3. host routing: a ``pallas`` region routed to ``host`` must return an
   array on the CPU device (the kernel runs interpreted there) that matches
   the TPU result;
4. serving: ``repro.launch.serve.main`` with the published ``gemma3-1b``
   (random weights) on the continuous-batching engine, 8 requests, every
   token checked against the teacher-forced solo decode's logits, then the
   same run with
   ``--offload-kv``, which must give the same tokens with its k/v pages in
   ``pinned_host``.

Four chips: ``repro.launch.scaling.main`` on a 2x2 mesh at 256x256x128
against its single-device replay, with parity, and the replayed fields
spread over all four devices.

Every phase asserts; nothing is caught. Timings printed are from a smoke
run, not a benchmark. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

CFD_GRID = 128
CFD_INNER_MAX = 5
SERVE_ARGS = ["--arch", "gemma3-1b", "--engine", "--slots", "4",
              "--requests", "8", "--prompt-len", "128", "--gen", "32"]
SCALING_ARGS = ["--apus", "4", "--mesh", "2x2", "--grid", "256,256,128"]


def device_check(chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[device] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev['platform']}")
    if dev["count"] < chips:
        raise SystemExit(f"--chips {chips} needs {chips} chips, JAX found "
                         f"{dev['count']}")
    return dev


def _max_err(a_fields, b_fields) -> float:
    import numpy as np
    return max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
               for a, b in zip(a_fields, b_fields))


def _fields(st):
    return (st.u, st.v, st.w, st.p)


def cfd_phase() -> None:
    import numpy as np
    from repro.cfd.grid import Grid
    from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
    from repro.core.regions import (DiscretePolicy, Executor, StaticSelector,
                                    SyncedExecutor, UnifiedPolicy)
    from repro.core.umem import space_of

    cfg = SimpleConfig(grid=Grid((CFD_GRID,) * 3), nu=0.1,
                       inner_max=CFD_INNER_MAX)
    app = SimpleFoam(cfg)
    st, fom, m = app.run_steps(init_state(cfg), 1)
    print(f"[cfd] {CFD_GRID}^3 run_steps(1): {fom:.3f} s (smoke timing, "
          f"compiles included); res_u {m['res_u']:.3e}", flush=True)
    prog = app.capture_step(st)
    print(f"[cfd] captured {prog.summary()}", flush=True)

    runs = {}
    for name, policy in (
            ("ref", UnifiedPolicy(selector=StaticSelector("ref"))),
            ("pallas", UnifiedPolicy(selector=StaticSelector("pallas"))),
            ("discrete", DiscretePolicy())):
        # the discrete replay reports a staging fraction: wait for every
        # call there, so its denominator is device-inclusive
        ex = (SyncedExecutor if name == "discrete" else Executor)(policy)
        app.replay_steps(prog, st, 1, ex)            # compiles
        ex.ledger.reset_timings()
        out, sps = app.replay_steps(prog, st, 2, ex)
        runs[name] = (out, ex)
        print(f"[cfd] replay under {name}: {sps:.4f} s/step (smoke timing, "
              f"2 steps after a warm-up step)", flush=True)

    ref_fields = _fields(runs["ref"][0])
    scale = max(float(np.max(np.abs(np.asarray(f)))) for f in ref_fields)
    tol = 1e-5 * max(scale, 1.0)
    for name in ("pallas", "discrete"):
        err = _max_err(_fields(runs[name][0]), ref_fields)
        print(f"[cfd] parity {name} vs ref: max abs err {err:.3e} "
              f"(tol {tol:.3e})", flush=True)
        assert np.isfinite(err) and err <= tol, (name, err, tol)

    rows = runs["pallas"][1].ledger.regions
    for region in ("Amul", "precondition(DILU)", "sA=rA-alpha*AyA",
                   "x+=a*yA+w*zA"):
        counts = {}
        for row_name, row in rows.items():
            if row_name.split("#")[0] == region:
                for impl, n in row.impl_counts.items():
                    counts[impl] = counts.get(impl, 0) + n
        print(f"[cfd] impl_counts {region}: {counts}", flush=True)
        assert counts.get("pallas", 0) > 0 and "ref" not in counts, \
            (region, counts)

    spaces = sorted({space_of(f) for f in _fields(runs["discrete"][0])})
    rep = runs["discrete"][1].report()
    print(f"[cfd] discrete replay results in {spaces}; staging fraction "
          f"{rep['staging_fraction']:.3f}", flush=True)
    assert spaces == ["pinned_host"], spaces


def host_routing_phase() -> None:
    import jax
    import numpy as np
    from repro.cfd import fvm
    from repro.cfd.dia import AMUL
    from repro.cfd.grid import Grid
    from repro.core.regions import (Executor, HostPolicy, StaticSelector,
                                    UnifiedPolicy, host_device)

    g = Grid((16, 16, 16))
    A, _ = fvm.laplacian(g, 1.0, dirichlet=[True] * 6)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 16, 16))
    on_tpu = Executor(UnifiedPolicy()).run(AMUL, A.diag, A.off, x)
    y = Executor(HostPolicy(selector=StaticSelector("pallas"))).run(
        AMUL, A.diag, A.off, x)
    devices = {str(d) for d in y.devices()}
    err = float(np.max(np.abs(np.asarray(y) - np.asarray(on_tpu))))
    print(f"[host] Amul(pallas) routed to host ran on {sorted(devices)}; "
          f"max abs err vs the TPU ref {err:.3e}", flush=True)
    assert y.devices() == {host_device()}, devices
    assert err <= 1e-5 * max(float(np.max(np.abs(np.asarray(on_tpu)))), 1.0)


def serve_phase() -> None:
    from repro.launch import serve
    from repro.serve.traffic import LOGIT_TOL_ULPS

    t0 = time.perf_counter()
    plain = serve.main(SERVE_ARGS)
    t1 = time.perf_counter()
    offload = serve.main(SERVE_ARGS + ["--offload-kv"])
    t2 = time.perf_counter()
    for name, m, dt in (("device KV", plain, t1 - t0),
                        ("--offload-kv", offload, t2 - t1)):
        answered = sum(1 for toks in m["outputs"].values() if toks)
        par = m["parity"]
        print(f"[serve] gemma3-1b {name}: {answered}/{m['requests']} "
              f"requests answered, {m['tokens']} tokens, slot KV in "
              f"{m['kv_spaces']}; {dt:.1f} s wall (smoke timing, compiles "
              f"included)", flush=True)
        print(f"[serve] gemma3-1b {name}: all {par['tokens']} tokens within "
              f"{LOGIT_TOL_ULPS} bf16 spacings of the teacher-forced solo "
              f"top logit (max {par['gap_max']:.1f}); {par['diverged']}/"
              f"{m['requests']} streams left the free-running solo decode",
              flush=True)
        assert m["requests"] == 8 and answered == 8, m["requests"]
    assert offload["outputs"] == plain["outputs"], "offload changed tokens"
    assert plain["kv_spaces"] == ["device"], plain["kv_spaces"]
    assert offload["kv_spaces"] == ["pinned_host"], offload["kv_spaces"]


def sharded_phase() -> None:
    from repro.launch import scaling

    rec = scaling.main(SCALING_ARGS)
    print(f"[scaling] mesh {rec['mesh_shape']} over {rec['field_devices']} "
          f"devices, field shards {rec['field_shard_shape']} of "
          f"{rec['grid']}; parity max abs err "
          f"{rec['parity_max_abs_err']:.3e} (tol {rec['parity_tol']:.3e}); "
          f"{rec['fom_single_s']:.4f} s/step single vs "
          f"{rec['fom_sharded_s']:.4f} s/step sharded (smoke timing)",
          flush=True)
    assert rec["parity_ok"], rec["parity_max_abs_err"]
    assert rec["report"]["devices"] == 4 and rec["field_devices"] == 4
    nx, ny, nz = rec["grid"]
    assert rec["field_shard_shape"] == [nx, ny // 2, nz // 2], \
        rec["field_shard_shape"]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the decomposed CFD replay on a 2x2 "
                         "mesh of four chips")
    args = ap.parse_args(argv)
    dev = device_check(args.chips)

    from repro.launch.compilation import configure_compilation
    print(f"[setup] compilation cache: {configure_compilation()}",
          flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase()
    else:
        cfd_phase()
        host_routing_phase()
        serve_phase()
    print(f"[done] {time.perf_counter() - t0:.1f} s wall", flush=True)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
