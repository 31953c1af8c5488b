"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (and mirrors them to a CSV
file, ``--out``). The CPU container cannot reproduce the paper's absolute
hardware numbers (4x vs H100 etc.); each benchmark reproduces the *claim
structure* on real measured work (see docs/DESIGN.md §8) — unified vs
discrete-managed vs host on identical region programs, migration fractions
and their async-overlap mitigation, offload coverage, pooling and cutoff
calibration — plus the roofline report over the dry-run artifacts.

  python benchmarks/run.py                      # everything
  python benchmarks/run.py --only fig6b_overlap,pool --out artifacts/bench.csv
"""
from __future__ import annotations

import argparse
import json
import os
import warnings

warnings.filterwarnings("ignore")
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROWS = []


def row(name: str, us_per_call: float, derived: str = ""):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


# ---------------------------------------------------------------------------
def fig5_speedup(steps: int = 3, grid=(16, 16, 16)):
    """Paper Fig 5: FOM (s/time-step) per execution policy, normalized.

    ``adaptive`` is the beyond-paper mode the regions API enables: the
    TARGET_CUT_OFF clause running *inside* an executor, with its host/device
    routing counts in the same coverage report as the staging fractions."""
    from repro.cfd.grid import Grid
    from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
    from repro.core.regions import (AdaptivePolicy, DiscretePolicy, Executor,
                                    HostPolicy, UnifiedPolicy)
    cfg = SimpleConfig(grid=Grid(grid), nu=0.1, inner_max=15)
    fom = {}
    policies = (("host", HostPolicy), ("discrete", DiscretePolicy),
                ("unified", UnifiedPolicy),
                ("adaptive", lambda: AdaptivePolicy(cutoff=1024)))
    for name, make in policies:
        app = SimpleFoam(cfg, executor=Executor(make()))
        st = init_state(cfg)
        st, _, _ = app.run_steps(st, 1)      # warm caches
        app.ledger.reset_timings()
        _, f, _ = app.run_steps(st, steps)
        fom[name] = f
        rep = app.ex.report()
        row(f"fig5/{name}_fom", f * 1e6,
            f"s_per_step={f:.4f};host_calls={rep['host_calls']}"
            f";device_calls={rep['device_calls']}")
    for name in ("host", "discrete"):
        row(f"fig5/speedup_unified_vs_{name}", 0.0,
            f"x{fom[name] / fom['unified']:.2f}")
    return fom


def fig6_migration(steps: int = 2, grid=(16, 16, 16)):
    """Paper Fig 6: fraction of step time in staging (page migration)."""
    from repro.cfd.grid import Grid
    from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
    from repro.core.regions import DiscretePolicy, Executor, UnifiedPolicy
    cfg = SimpleConfig(grid=Grid(grid), nu=0.1, inner_max=15)
    for name, cls in (("discrete", DiscretePolicy),
                      ("unified", UnifiedPolicy)):
        app = SimpleFoam(cfg, executor=Executor(cls()))
        st = init_state(cfg)
        st, _, _ = app.run_steps(st, 1)
        app.ledger.reset_timings()
        app.run_steps(st, steps)
        rep = app.ex.report()
        row(f"fig6/{name}_staging", rep["staging_s"] * 1e6 / max(steps, 1),
            f"fraction={rep['staging_fraction']:.3f}")


def fig6b_overlap(steps: int = 2, grid=(16, 16, 16)):
    """Beyond-paper Fig 6b: the discrete staging storm with one-step
    lookahead (repro.core.program).  One SIMPLE step is captured as a
    RegionProgram and replayed under DiscretePolicy twice — synchronously
    (Executor) and with double-buffered prefetch (AsyncExecutor).  The two
    replays must agree bit-for-bit; the async one reports how much of the
    migration storm was hidden behind compute.  On a CPU-only container the
    prefetch thread and "device" compute share the same cores, so the FOM
    here is overlap_fraction / staging_saved_s, not wall-clock — the
    wall-clock win needs a real copy engine."""
    from repro.cfd.grid import Grid
    from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
    from repro.core.program import AsyncExecutor
    from repro.core.regions import DiscretePolicy, Executor
    cfg = SimpleConfig(grid=Grid(grid), nu=0.1, inner_max=15)
    app = SimpleFoam(cfg)
    st = init_state(cfg)
    st, _, _ = app.run_steps(st, 1)              # develop flow + warm caches
    prog = app.capture_step(st)
    sync = Executor(DiscretePolicy())
    asyn = AsyncExecutor(DiscretePolicy())
    app.replay_steps(prog, st, 1, sync)          # warm per-target caches
    app.replay_steps(prog, st, 1, asyn)
    sync.ledger.reset_timings()
    asyn.ledger.reset_timings()
    s_sync, f_sync = app.replay_steps(prog, st, steps, sync)
    s_asyn, f_asyn = app.replay_steps(prog, st, steps, asyn)
    for a, b in zip((s_sync.u, s_sync.v, s_sync.w, s_sync.p),
                    (s_asyn.u, s_asyn.v, s_asyn.w, s_asyn.p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rep = asyn.report()
    row("fig6b/sync_replay_fom", f_sync * 1e6,
        f"staging_fraction={sync.report()['staging_fraction']:.3f}")
    row("fig6b/async_replay_fom", f_asyn * 1e6,
        f"overlap_fraction={rep['overlap_fraction']:.3f}"
        f";staging_saved_s={rep['staging_saved_s']:.4f}"
        f";speedup=x{f_sync / max(f_asyn, 1e-12):.2f}")
    assert rep["overlap_fraction"] > 0, rep      # acceptance criterion
    return rep


def _scaling_mesh_shape(n: int) -> tuple:
    """Mesh shape for an n-APU node: the shared near-square 2-D
    factorization (``repro.launch.mesh.near_square_mesh_shape`` — also
    the autotuner's mesh-shape axis) to cut surface-to-volume.
    FIG_SCALING_MESH=1d forces the 1-D baseline."""
    import os

    from repro.launch.mesh import near_square_mesh_shape
    if os.environ.get("FIG_SCALING_MESH", "auto") == "1d":
        return (n,)
    return near_square_mesh_shape(n)


def fig_scaling(steps: int = 2, grid="8,8,8", policy="unified"):
    """Beyond-paper scaling figure: the captured SIMPLE step replayed
    domain-decomposed over 1/2/4/8 simulated APUs
    (repro.core.shard_program + repro.launch.scaling), strong- AND
    weak-scaling, under the overlapped wide-halo exchange schedule.

    On forced CPU devices each node size runs in a fresh subprocess — the
    APU count must be in XLA_FLAGS before the first jax import, and this
    process has already imported jax with one device; on real chips it
    runs in this process (``repro.launch.scaling.run``).  Every run
    asserts single- vs multi-device numerical parity (docs/DESIGN.md §2
    tolerance) and the derived column carries the node-level
    compute/staging/exchange/overlap split from the aggregated
    per-device ledgers.  On a CPU container all
    "APUs" share the same cores, so the FOM here is the exchange
    accounting and the parity guarantee, not wall-clock speedup (see
    docs/SCALING.md).

    Regression gate (CI): every multi-APU run must keep its EXPOSED
    exchange fraction under the pinned budget and, under the overlapped
    schedule, must actually hide exchange time (``overlap_s > 0``) — the
    halo-exchange-tax fix is locked in here.  Knobs: FIG_SCALING_APUS=1,2
    FIG_SCALING_GRID=16,16,16 FIG_SCALING_SCHEDULE=overlap|sequential|split
    FIG_SCALING_HALO=2 FIG_SCALING_MESH=auto|1d FIG_SCALING_BUDGET=0.15."""
    import os

    from repro.launch import scaling
    apus = [int(x) for x in
            os.environ.get("FIG_SCALING_APUS", "1,2,4,8").split(",") if x]
    grid = os.environ.get("FIG_SCALING_GRID", grid)
    schedule = os.environ.get("FIG_SCALING_SCHEDULE", "overlap")
    halo_mult = os.environ.get("FIG_SCALING_HALO", "2")
    budget = float(os.environ.get("FIG_SCALING_BUDGET", "0.15"))
    base_grid = tuple(int(g) for g in grid.split(","))

    def run_one(n, grid_t, out_name, row_name, base):
        mesh_shape = _scaling_mesh_shape(n)
        out = Path(f"artifacts/scaling/{out_name}.json")
        out.parent.mkdir(parents=True, exist_ok=True)
        argv = ["--apus", str(n), "--mesh",
                "x".join(str(s) for s in mesh_shape),
                "--steps", str(steps),
                "--grid", ",".join(str(g) for g in grid_t),
                "--policy", policy, "--schedule", schedule,
                "--halo-multiplier", halo_mult, "--inner-max", "6"]
        try:
            rec = scaling.run(argv, out)
        except RuntimeError as e:
            row(row_name, 0.0, f"FAILED:{str(e).strip()[-160:]}")
            raise RuntimeError(f"fig_scaling failed for {n} APUs") from e
        assert rec["parity_ok"], rec          # acceptance criterion
        rep = rec["report"]
        dev0 = rep["per_device"][0]
        row(row_name, rec["fom_sharded_s"] * 1e6,
            f"parity_max_err={rec['parity_max_abs_err']:.2e}"
            f";mesh={'x'.join(str(s) for s in rec['mesh_shape'])}"
            f";compute_s={rep['compute_s']:.4f}"
            f";staging_s={rep['staging_s']:.4f}"
            f";exchange_s={rep['exchange_s']:.4f}"
            f";overlap_s={rep['overlap_s']:.4f}"
            f";exchange_fraction={rep['exchange_fraction']:.3f}"
            f";exchange_bytes={rep['exchange_bytes']}"
            f";dev0_compute_s={dev0['compute_s']:.4f}"
            f";dev0_exchange_s={dev0['exchange_s']:.4f}"
            f";vs_base=x{rec['fom_sharded_s'] / max(base or rec['fom_sharded_s'], 1e-12):.2f}")
        if n > 1:
            # the regression gate: exposed exchange stays under the pinned
            # budget, and the overlapped schedule actually hides time
            assert rep["exchange_fraction"] <= budget, (
                f"exchange_fraction {rep['exchange_fraction']:.3f} over "
                f"budget {budget} for {n} APUs ({row_name})")
            if schedule != "sequential":
                assert rep["overlap_s"] > 0.0, (
                    f"no exchange overlap recorded for {n} APUs "
                    f"({row_name}): {rep['overlap_s']}")
        return rec

    # strong scaling: fixed grid, growing node
    base = None
    for n in apus:
        rec = run_one(n, base_grid, f"apu{n}", f"fig_scaling/apus{n}", base)
        if base is None:
            base = rec["fom_sharded_s"]

    # weak scaling: constant cells/APU — the decomposed dims grow with
    # their mesh axes, so exchange surface per APU stays fixed while node
    # volume grows (the JSONs land next to the strong-scaling artifacts)
    wbase = None
    for n in apus:
        mesh_shape = _scaling_mesh_shape(n)
        wgrid = list(base_grid)
        for dim, s in zip(range(-len(mesh_shape), 0), mesh_shape):
            wgrid[dim] *= s
        rec = run_one(n, tuple(wgrid), f"weak_apu{n}",
                      f"fig_scaling/weak_apus{n}", wbase)
        if wbase is None:
            wbase = rec["fom_sharded_s"]
    return apus


def fig_variants(steps: int = 2, grid=(12, 12, 12),
                 out_json="artifacts/variants/autotune_winners.json"):
    """Beyond-paper variants figure: the captured SIMPLE step replayed
    under StaticSelector('ref'), StaticSelector('pallas'), and a
    calibrated AutotuneSelector, per policy (repro.core.regions Selector
    axis — the 'which implementation' half of the paper's one-directive
    claim).  Asserts DESIGN §2 parity across selectors, prints the
    impl_counts proving which variant ran where, and writes the autotune
    winners JSON next to the CSV.  On a CPU container the Pallas kernels
    run in interpret mode, so the FOM here is the dispatch/accounting
    structure and the measured per-cell winners, not kernel wall-clock.
    Calibration grid edges override via FIG_VARIANTS_SIZES=8,12."""
    import os
    from repro.cfd import fvm
    from repro.cfd.grid import Grid
    from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
    from repro.core.regions import (AutotuneSelector, Executor,
                                    StaticSelector, make_policy)
    edges = [int(x) for x in
             os.environ.get("FIG_VARIANTS_SIZES", "8,12,16").split(",") if x]
    cfg = SimpleConfig(grid=Grid(grid), nu=0.1, inner_max=10)
    app = SimpleFoam(cfg)
    st = init_state(cfg)
    st, _, _ = app.run_steps(st, 1)
    prog = app.capture_step(st)

    # calibrate the solver hot-spot regions over a grid-edge ladder
    auto = AutotuneSelector()
    sizes_cells = []
    for m in edges:
        g = Grid((m, m, m))
        A, _ = fvm.laplacian(g, 1.0)
        x = jnp.ones(g.shape, jnp.float32)
        red, _ = g.red_black_masks()
        from repro.cfd.precond import rb_dilu_factor
        P = rb_dilu_factor(A, red)
        # both routing targets: UnifiedPolicy routes offloaded regions to
        # "default", DiscretePolicy to "device" — winners are per-target
        # cells, so calibrating only one would leave the other on ref
        auto.calibrate(app.solver_regions.amul,
                       lambda n, A=A, x=x: (A.diag, A.off, x),
                       sizes=(g.n,), targets=("default", "device"), reps=3)
        auto.calibrate(app.solver_regions.precond,
                       lambda n, P=P, A=A, x=x: (P.rdiag, P.red, A.off, x),
                       sizes=(g.n,), targets=("default", "device"), reps=3)
        sizes_cells.append(g.n)
    winners = {f"{rn}|{tgt}|2^{b}": win
               for (rn, tgt, b), win in sorted(auto.winners.items())}

    selectors = (("ref", StaticSelector("ref")),
                 ("pallas", StaticSelector("pallas")),
                 ("autotuned", auto))
    base = {}
    for pol_name in ("unified", "discrete"):
        for sel_name, sel in selectors:
            pol = make_policy(pol_name)
            pol.selector = sel
            ex = Executor(pol)
            app.replay_steps(prog, st, 1, ex)          # warm compiles
            ex.ledger.reset_timings()
            s, fom = app.replay_steps(prog, st, steps, ex)
            fields = [np.asarray(f) for f in (s.u, s.v, s.w, s.p)]
            ref_fields = base.setdefault(pol_name, fields)
            scale = max(np.max(np.abs(f)) for f in ref_fields)
            err = max(np.max(np.abs(a - b))
                      for a, b in zip(fields, ref_fields))
            assert err <= 1e-5 * max(1.0, scale), \
                (pol_name, sel_name, err)              # DESIGN §2 parity
            counts = ex.report()["impl_counts"]
            # calibration persisted on the app ledger's region rows
            wins = app.ledger.coverage_report()["variant_wins"]
            row(f"fig_variants/{pol_name}_{sel_name}", fom * 1e6,
                f"impl_counts={'+'.join(f'{k}:{v}' for k, v in sorted(counts.items()))}"
                f";parity_max_err={err:.2e}"
                f";variant_wins={'+'.join(f'{k}:{v}' for k, v in sorted(wins.items()))}")
    out = Path(out_json)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(
        {"calibration_grid_edges": edges, "calibration_sizes": sizes_cells,
         "winners": winners,
         "bucket_model": "b covers sizes in [2^(b-1), 2^b)"}, indent=1))
    print(f"[bench] wrote autotune winners to {out}", flush=True)
    return winners


def fig4_coverage(grid=(12, 12, 12)):
    """Paper Figs 2 vs 4: offload coverage, PETSc-interface mode (assembly
    on host, solver offloaded) vs full directive mode."""
    from repro.cfd.grid import Grid
    from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
    cfg = SimpleConfig(grid=Grid(grid), nu=0.1, inner_max=15)
    for name, host_asm in (("petsc_mode", True), ("directive_mode", False)):
        app = SimpleFoam(cfg, assemble_on_host=host_asm)
        st = init_state(cfg)
        st, _, _ = app.run_steps(st, 1)
        app.ledger.reset_timings()
        app.run_steps(st, 2)
        rep = app.ledger.coverage_report()
        row(f"fig4/{name}", rep["total_s"] * 1e6,
            f"device_fraction={rep['device_fraction']:.3f}"
            f";regions={rep['offloaded_regions']}/{rep['regions']}")


def fig_serve(batch: int = 2, prompt_len: int = 12, gen: int = 8,
              out_json: str = "artifacts/serve/fig_serve.json"):
    """Beyond-paper serving figure: the LM request path on the region
    spine (PREFILL / DECODE_STEP / KV_APPEND captured as RegionPrograms,
    repro.launch.serve) replayed under unified vs discrete vs
    offloaded-KV policies — ONE captured trace, three policies — with the
    per-policy coverage_report() in the derived column and every token
    sequence parity-asserted against the pre-capture jit path.  Also
    measures the decode stream with a per-token block_until_ready vs one
    sync per interval (the retired per-token sync serialized the stream)
    and records the reclaimed latency.  On a CPU-only container XLA's
    dispatch is effectively synchronous, so ``reclaimed_ms`` ~ 0 there —
    the row records the claim structure; the win needs a real async
    device stream (same caveat as fig6b's wall-clock)."""
    from types import SimpleNamespace

    from repro.configs.reduced import reduced as make_reduced
    from repro.configs.registry import get_config
    from repro.core.ledger import Ledger
    from repro.core.regions import Executor
    from repro.launch import serve as SV
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.policy import lm_policy
    from repro.models import transformer as T

    cfg = make_reduced(get_config("tinyllama-1.1b"))
    mesh = make_smoke_mesh()
    max_len = prompt_len + gen
    key = jax.random.PRNGKey(0)
    params = T.init(key, cfg)
    prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab,
                                 jnp.int32)
    ns = SimpleNamespace(batch=batch, prompt_len=prompt_len, gen=gen)
    batch_in = SV._prefill_inputs(cfg, ns, prompts)

    # -- pre-capture jit path: parity reference + stream-sync measurement
    prefill_j, decode_j, make_cache = SV.build_server(cfg, mesh, batch,
                                                      max_len)
    logits, cache_w = prefill_j(params, batch_in, make_cache())
    tok0 = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    jax.block_until_ready(
        decode_j(params, tok0, cache_w, jnp.int32(prompt_len)))  # warm
    stream_ms = {}
    seq_ref = None
    for sync_name, sync_every in (("per_token", 1), ("interval", 0)):
        best = float("inf")
        for _ in range(3):
            _, cache_s = prefill_j(params, batch_in, make_cache())
            t0 = time.perf_counter()
            toks_s, _ = SV.decode_stream(decode_j, params, tok0, cache_s,
                                         prompt_len, gen,
                                         sync_every=sync_every)
            best = min(best, time.perf_counter() - t0)
        stream_ms[sync_name] = best * 1e3
        seq_ref = np.asarray(jnp.stack(toks_s, axis=1))
    reclaimed = stream_ms["per_token"] - stream_ms["interval"]
    row("fig_serve/stream_sync", stream_ms["interval"] * 1e3 / gen,
        f"per_token_ms={stream_ms['per_token']:.2f}"
        f";interval_ms={stream_ms['interval']:.2f}"
        f";reclaimed_ms={reclaimed:.2f}")

    # -- the serving spine: capture ONCE, replay under every policy ------
    regions = SV.make_serve_regions(cfg, mesh, params,
                                    ledger=Ledger("serve_bench"))
    prefill_prog = SV.capture_prefill_program(
        regions, batch_in, T.init_cache(cfg, batch, max_len))
    tok_ex, cache_ex = prefill_prog.replay(
        Executor(lm_policy("unified", cfg.memory), Ledger("warm")),
        batch_in, T.init_cache(cfg, batch, max_len))
    decode_prog = SV.capture_decode_program(regions, prompt_len, gen,
                                            tok_ex, cache_ex)
    reports = {}
    policies = (
        ("unified", lambda: lm_policy("unified", cfg.memory)),
        ("discrete", lambda: lm_policy("discrete", cfg.memory)),
        ("offload_kv", lambda: lm_policy("unified", cfg.memory,
                                         placer=SV.offload_kv_cache())),
    )
    for name, make_pol in policies:
        ex = Executor(make_pol(), Ledger(f"serve_{name}"))
        tok, cache = prefill_prog.replay(ex, batch_in,
                                         T.init_cache(cfg, batch, max_len))
        decode_prog.replay(ex, tok, cache)          # warm per-target caches
        ex.ledger.reset_timings()
        t0 = time.perf_counter()
        toks = decode_prog.replay(ex, tok, cache)
        t_decode = time.perf_counter() - t0
        seq = np.asarray(jnp.stack(toks, axis=1))
        # capture changes the schedule, never the tokens: every policy's
        # sequence must match the pre-capture jit path bit-for-bit
        np.testing.assert_array_equal(seq, seq_ref, err_msg=name)
        rep = ex.report()
        reports[name] = rep
        row(f"fig_serve/{name}", t_decode * 1e6 / gen,
            f"device_fraction={rep['device_fraction']:.3f}"
            f";staging_fraction={rep['staging_fraction']:.3f}"
            f";impl_counts={'+'.join(f'{k}:{v}' for k, v in sorted(rep['impl_counts'].items()))}"
            f";parity=exact")
    out = Path(out_json)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(
        {"batch": batch, "prompt_len": prompt_len, "gen": gen,
         "stream_ms": stream_ms, "reclaimed_ms": reclaimed,
         "reports": reports}, indent=1, default=str))
    print(f"[bench] wrote serve reports to {out}", flush=True)
    return reports


def fig_traffic(requests: int = 8, slots: int = 4, rate: float = 2.0,
                out_json: str = "artifacts/traffic/fig_traffic.json"):
    """Continuous-batching traffic figure (docs/SERVING.md).

    Seeded Poisson arrivals with ragged prompt/gen lengths pushed through
    the ``ServeEngine`` (slot scheduler + paged KV over the region spine)
    under unified / discrete / offloaded-KV policies, against two
    references on the SAME traffic:

    * ``sequential``: the engine with one slot — solo decodes in arrival
      order through the identical spine; the continuous-batching win is
      engine tokens/s strictly above this (asserted);
    * the solo jit path (``build_server`` + ``decode_stream``): the
      bit-parity oracle — every engine token sequence must match it
      exactly, under every policy (asserted).

    A final run caps the device page budget below one parked prefill so
    the paged store spills to host DRAM mid-traffic: the artifact records
    pages spilled/fetched and the device high-water, and parity must
    survive the crossing (the paper's oversubscription story applied to
    serving)."""
    from repro.configs.reduced import reduced as make_reduced
    from repro.configs.registry import get_config
    from repro.core.ledger import Ledger
    from repro.core.regions import Executor
    from repro.launch import serve as SV
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.policy import lm_policy
    from repro.models import transformer as T
    from repro.serve import (PagedKVCache, ServeEngine, make_traffic,
                             run_traffic, solo_reference)
    from repro.serve.traffic import assert_parity

    cfg = make_reduced(get_config("tinyllama-1.1b"))
    mesh = make_smoke_mesh()
    params = T.init(jax.random.PRNGKey(0), cfg)
    max_len = 18                                 # fits 10-prompt + 8-gen

    def traffic():
        return make_traffic(seed=3, n_requests=requests, vocab=cfg.vocab,
                            arrival_rate=rate, prompt_lens=(6, 10),
                            gen_lens=(1, 5, 8))

    reqs0 = traffic()
    oracle, solo_wall = solo_reference(cfg, mesh, params, reqs0, max_len)
    n_tokens = sum(len(v) for v in oracle.values())
    solo_tps = n_tokens / max(solo_wall, 1e-9)

    def run(name, policy, n_slots, **kv_kwargs):
        ex = Executor(policy, Ledger(f"traffic_{name}"))
        kv = PagedKVCache(page_tokens=4, **kv_kwargs)
        eng = ServeEngine(cfg, mesh, params, ex, max_len=max_len,
                          n_slots=n_slots, kv=kv)
        reqs = traffic()
        metrics = run_traffic(eng, reqs)
        assert_parity(reqs, oracle)              # the invariant, per policy
        rep = ex.ledger.coverage_report()
        rec = {**metrics, "n_slots": n_slots, "kv": kv.stats.as_dict(),
               "serve": rep.get("serve", {}),
               "pools": {k: v for k, v in rep.get("pools", {}).items()}}
        row(f"fig_traffic/{name}",
            metrics["wall_s"] * 1e6 / max(metrics["tokens"], 1),
            f"tokens_per_s={metrics['tokens_per_s']:.0f}"
            f";occupancy={rep['serve'].get('slot_occupancy', 0):.2f}"
            f";evictions={metrics['evictions']}"
            f";spilled={kv.stats.pages_spilled};parity=exact")
        return rec

    results = {"sequential": run("sequential",
                                 lm_policy("unified", cfg.memory), 1)}
    for name, pol in (
            ("unified", lm_policy("unified", cfg.memory)),
            ("discrete", lm_policy("discrete", cfg.memory)),
            ("offload_kv", lm_policy("unified", cfg.memory,
                                     placer=SV.offload_kv_cache(
                                         min_bytes=0)))):
        results[name] = run(name, pol, slots)

    # the continuous-batching claim: batched slots beat sequential solo
    # decodes through the identical spine on the identical traffic
    assert results["unified"]["tokens_per_s"] > \
        results["sequential"]["tokens_per_s"], \
        (results["unified"]["tokens_per_s"],
         results["sequential"]["tokens_per_s"])

    # oversubscription: device page budget below one parked prefill
    results["spill"] = run("spill", lm_policy("unified", cfg.memory),
                           slots, device_budget_bytes=512)
    assert results["spill"]["kv"]["pages_spilled"] > 0

    out = Path(out_json)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(
        {"requests": requests, "slots": slots, "rate": rate,
         "solo_jit_tokens_per_s": solo_tps, "runs": results},
        indent=1, default=str))
    print(f"[bench] wrote traffic figure to {out}", flush=True)
    return results


def fig_oversub(out_json: str = "artifacts/oversub/fig_oversub.json"):
    """Throughput vs oversubscription ratio — run what doesn't fit.

    Three workloads whose working sets exceed a logical device budget
    (``MemoryBudget.for_ratio(footprint, r)``, ratios from the
    ``FIG_OVERSUB_RATIOS`` env, default ``1,2,4``; ratio 1 is the
    everything-fits reference point):

    * **serve** — KV caches beyond the device budget under real seeded
      traffic: the paged store spills/evicts mid-stream, under unified /
      discrete / adaptive execution policies;
    * **moe** — host-resident expert weights (qwen3-moe structure with a
      sparse 16-expert/top-2 router) paged per token through a budgeted
      LRU working set;
    * **cfd** — a SIMPLE grid replayed under discrete and adaptive
      policies whose staging streams in budget-sized slabs.

    Gates (the paper's oversubscription claim on the logical budget):
    every budgeted run COMPLETES — degradation, never OOM — and parity
    holds against the unbudgeted reference at every ratio: serve tokens
    bitwise vs the solo jit oracle, moe outputs and cfd fields bitwise vs
    their ratio-independent references.  At ratios >= 4 the serve curve
    must actually spill (ratio 2 equals the parked-page peak for this
    traffic, so 4x is the first ratio past it).  ``REPRO_TRAFFIC_SEED``
    and ``FIG_OVERSUB_REQUESTS`` shape the traffic."""
    import dataclasses as _dc

    from repro.configs.reduced import reduced as make_reduced
    from repro.configs.registry import get_config
    from repro.core.ledger import Ledger
    from repro.core.oversub import MemoryBudget, workload_bytes
    from repro.core.regions import AdaptivePolicy, DiscretePolicy, Executor
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.policy import lm_policy
    from repro.models import moe as M
    from repro.models import transformer as T
    from repro.models.params import init_params
    from repro.serve import (PagedKVCache, ServeEngine, make_traffic,
                             run_traffic, solo_reference)
    from repro.serve.traffic import assert_parity

    ratios = [float(r) for r in os.environ.get(
        "FIG_OVERSUB_RATIOS", "1,2,4").split(",") if r]
    n_requests = int(os.environ.get("FIG_OVERSUB_REQUESTS", "6"))
    seed = int(os.environ.get("REPRO_TRAFFIC_SEED", "11"))
    results = {"ratios": ratios, "seed": seed,
               "serve": {}, "moe": [], "cfd": {}}

    # ---- (b) serving: KV caches larger than the device budget ----------
    cfg = make_reduced(get_config("tinyllama-1.1b"))
    mesh = make_smoke_mesh()
    params = T.init(jax.random.PRNGKey(0), cfg)
    max_len, slots = 16, 2

    def traffic():
        return make_traffic(seed=seed, n_requests=n_requests,
                            vocab=cfg.vocab, arrival_rate=2.0,
                            prompt_lens=(6, 10), gen_lens=(1, 5))

    oracle, _ = solo_reference(cfg, mesh, params, traffic(), max_len)
    probe = PagedKVCache(page_tokens=4)
    probe.commit(0, T.init_cache(cfg, 1, max_len), true_len=max_len)
    kv_fp = probe.total_bytes * slots
    probe.free(0)

    for mode in ("unified", "discrete", "adaptive"):
        curve = []
        for r in ratios:
            budget = MemoryBudget.for_ratio(kv_fp, r, name="kv")
            ex = Executor(lm_policy(mode, cfg.memory),
                          Ledger(f"oversub_{mode}_{r:g}"))
            kv = PagedKVCache(page_tokens=4, budget=budget)
            eng = ServeEngine(cfg, mesh, params, ex, max_len=max_len,
                              n_slots=slots, kv=kv)
            reqs = traffic()
            m = run_traffic(eng, reqs)
            assert_parity(reqs, oracle)          # completed AND bit-exact
            if r >= 4:
                assert kv.stats.pages_spilled > 0, \
                    f"ratio {r:g} should exceed the parked-page peak"
            curve.append({"ratio": r, "tokens_per_s": m["tokens_per_s"],
                          "evictions": m["evictions"],
                          "kv": kv.stats.as_dict(),
                          "budget": budget.as_dict()})
            row(f"fig_oversub/serve_{mode}_r{r:g}",
                m["wall_s"] * 1e6 / max(m["tokens"], 1),
                f"tokens_per_s={m['tokens_per_s']:.0f}"
                f";spilled={kv.stats.pages_spilled}"
                f";pressure={budget.stats.pressure_events};parity=exact")
        results["serve"][mode] = curve

    # ---- (a) MoE decode: experts paged per token through the budget ----
    mcfg = make_reduced(get_config("qwen3-moe-30b-a3b"))
    # reduced() caps MoE at 8 experts / top-8 (dense); restore a sparse
    # router so paging a partial working set is meaningful
    mcfg = _dc.replace(mcfg, moe=_dc.replace(mcfg.moe, n_experts=16,
                                             top_k=2, d_ff=32))
    p = init_params(jax.random.PRNGKey(0), M.moe_specs(mcfg))
    xs = [jax.random.normal(jax.random.PRNGKey(100 + t),
                            (1, 1, mcfg.d_model), mcfg.compute_dtype)
          for t in range(8)]

    def moe_stream(budget):
        pager = M.ExpertPager(p, mcfg, budget=budget)
        t0 = time.perf_counter()
        ys = [np.asarray(M.moe_decode_paged(pager, x, mcfg)[0])
              for x in xs]
        return pager, ys, time.perf_counter() - t0

    pager_ref, ref_ys, _ = moe_stream(None)      # warm + reference
    moe_fp = pager_ref.footprint_bytes
    for r in ratios:
        budget = MemoryBudget.for_ratio(moe_fp, r, name="moe")
        pager, ys, wall = moe_stream(budget)
        for a, b in zip(ref_ys, ys):             # paging moves bytes, not math
            np.testing.assert_array_equal(a, b)
        results["moe"].append({
            "ratio": r, "tokens_per_s": len(xs) / max(wall, 1e-9),
            "paging": pager.stats.as_dict(), "budget": budget.as_dict()})
        row(f"fig_oversub/moe_r{r:g}", wall * 1e6 / len(xs),
            f"fetches={pager.stats.fetches}"
            f";evictions={pager.stats.evictions};parity=exact")

    # ---- (c) CFD: grids beyond device capacity via budgeted staging ----
    from repro.cfd.grid import Grid
    from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
    ccfg = SimpleConfig(grid=Grid((12, 12, 12)), nu=0.1, inner_max=6)
    app = SimpleFoam(ccfg)
    st = init_state(ccfg)
    st, _, _ = app.run_steps(st, 1)
    prog = app.capture_step(st)
    cfd_fp = workload_bytes(st)
    for mode, make in (("discrete", DiscretePolicy),
                       ("adaptive", AdaptivePolicy)):
        s_ref, _ = app.replay_steps(prog, st, 2, Executor(make()))
        curve = []
        for r in ratios:
            budget = MemoryBudget.for_ratio(cfd_fp, r, name="cfd")
            s_b, fom = app.replay_steps(prog, st, 2,
                                        Executor(make(budget=budget)))
            for nm in ("u", "v", "w", "p"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(s_ref, nm)),
                    np.asarray(getattr(s_b, nm)))
            curve.append({"ratio": r, "fom_s_per_step": fom,
                          "budget": budget.as_dict()})
            row(f"fig_oversub/cfd_{mode}_r{r:g}", fom * 1e6,
                f"chunks={budget.stats.staging_chunks}"
                f";pressure={budget.stats.pressure_events};parity=exact")
        results["cfd"][mode] = curve

    out = Path(out_json)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(results, indent=1, default=str))
    print(f"[bench] wrote oversubscription figure to {out}", flush=True)
    return results


def fig_tune(out_json: str = "", bench_json: str = "BENCH_pr10.json"):
    """Global policy autotuner figure + the perf-trajectory gate.

    Runs the ``repro.tune`` search per workload (serve decode traffic,
    train step, CFD replay, sharded CFD), persists the warm-start
    profile, and reports the tuned winner's measured FOM against the
    hand-assembled reference policy each workload names (the paper's
    managed-dGPU baseline for the replay workloads, the PR-3
    sequential 1-D slab decomposition for the sharded one).  The gate
    locks the trajectory in: any tuned winner measurably worse than its
    reference beyond ``FIG_TUNE_TOL`` (or fewer than 2 strict wins
    across the suite) exits non-zero, so CI catches a cost model or
    search regression before it ships.  The canonical machine-readable
    record lands in ``BENCH_pr10.json`` at the repo root.

    Env knobs: FIG_TUNE_WORKLOADS (csv), FIG_TUNE_TRIALS,
    FIG_TUNE_STEPS, FIG_TUNE_TOL, FIG_TUNE_PROFILE, FIG_TUNE_MIN_WINS.
    """
    from repro.tune.profile import DEFAULT_PROFILE_PATH
    from repro.tune.tuner import tune_workloads
    names = [n for n in os.environ.get(
        "FIG_TUNE_WORKLOADS",
        "cfd_step,serve_decode,train_step,cfd_sharded").split(",") if n]
    trials = int(os.environ.get("FIG_TUNE_TRIALS", "2"))
    steps = int(os.environ.get("FIG_TUNE_STEPS", "0")) or None
    tol = float(os.environ.get("FIG_TUNE_TOL", "0.25"))
    min_wins = int(os.environ.get("FIG_TUNE_MIN_WINS",
                                  str(min(2, len(names)))))
    prof_path = os.environ.get("FIG_TUNE_PROFILE", DEFAULT_PROFILE_PATH)

    profile, results = tune_workloads(names, trials=trials, steps=steps,
                                      out=prof_path, gate_tol=None)
    cells, failures, wins = [], [], 0
    for res in results:
        fom, ref = res.fom_s, res.ref_fom_s
        speedup = (ref / max(fom, 1e-12)) if fom and ref else None
        strict_win = bool(fom and ref and fom < ref)
        wins += strict_win
        if fom and ref and fom > ref * (1.0 + tol):
            failures.append(f"{res.workload}: tuned {fom:.3e}s vs ref "
                            f"{ref:.3e}s exceeds tol {tol:g}")
        cells.append({
            "workload": res.workload, "bucket": res.bucket,
            "winner": res.winner.label, "candidate": res.winner.to_dict(),
            "fom_s": fom, "ref_fom_s": ref, "score_s": res.score_s,
            "speedup_vs_ref": speedup, "strict_win": strict_win,
            "disqualified": res.disqualified,
            "candidates_scored": len(res.table),
        })
        row(f"fig_tune/{res.workload}", (fom or 0.0) * 1e6,
            f"winner={res.winner.label}"
            + (f";x{speedup:.2f}_vs_ref" if speedup else "")
            + (";WIN" if strict_win else ""))
    if wins < min_wins:
        failures.append(f"only {wins} strict tuned-vs-ref wins, "
                        f"gate requires >= {min_wins}")
    gate = {"tol": tol, "min_wins": min_wins, "strict_wins": wins,
            "ok": not failures, "failures": failures}
    rec = {
        "bench": "fig_tune",
        "generated_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "profile": prof_path,
        "trials": trials,
        "workloads": cells,
        "gate": gate,
    }
    for path in (bench_json, out_json):
        if path:
            p = Path(path)
            if p.parent != Path("."):
                p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(json.dumps(rec, indent=1, default=str) + "\n")
    print(f"[bench] wrote tuned-vs-ref figure to {bench_json}"
          f" (profile: {prof_path})", flush=True)
    row("fig_tune/gate", 0.0,
        f"wins={wins}/{len(names)};tol={tol:g};"
        f"{'ok' if gate['ok'] else 'FAIL'}")
    if failures:
        raise SystemExit("[fig_tune] perf-trajectory gate failed: "
                         + "; ".join(failures))
    return rec


def pool_bench(n: int = 200, shape=(1 << 20,)):
    """Umpire pooling (paper §5): alloc+touch latency, pooled vs malloc."""
    from repro.core.pool import HostStagingPool
    pool = HostStagingPool()
    a = pool.acquire(shape, np.float32)
    pool.release(a)
    t0 = time.perf_counter()
    for _ in range(n):
        b = pool.acquire(shape, np.float32)
        b[0] = 1.0
        pool.release(b)
    t_pool = (time.perf_counter() - t0) / n
    t0 = time.perf_counter()
    for _ in range(n):
        b = np.empty(shape, np.float32)
        b[0] = 1.0
        del b
    t_malloc = (time.perf_counter() - t0) / n
    row("pool/pooled_acquire", t_pool * 1e6,
        f"hit_rate={pool.stats.hit_rate:.2f}")
    row("pool/malloc_acquire", t_malloc * 1e6,
        f"speedup=x{t_malloc / max(t_pool, 1e-12):.2f}")


def dispatch_bench():
    """TARGET_CUT_OFF calibration (listings 4-6) on the regions API — a
    Region driven by AdaptivePolicy; the chosen cutoff is recorded with
    the region's ledger row and the routing decisions land in the same
    coverage report as staging fractions."""
    from repro.core.ledger import Ledger
    from repro.core.regions import AdaptivePolicy, Executor, region
    ldg = Ledger("dispatch")
    saxpy = region("saxpy", ledger=ldg)(lambda x: x * 2.0 + 1.0)
    pol = AdaptivePolicy()
    cut = pol.calibrate(saxpy, lambda n: (jnp.ones(n),),
                        sizes=(256, 1024, 4096, 16384, 65536, 262144),
                        ledger=ldg)
    ex = Executor(pol, ldg)
    ex.run(saxpy, jnp.ones(max(cut // 2, 1)))     # below cutoff -> host
    ex.run(saxpy, jnp.ones(2 * cut))              # above cutoff -> device
    rep = ldg.coverage_report()
    row("dispatch/target_cutoff", 0.0,
        f"cutoff={cut};ledger={rep['cutoffs']}"
        f";host_calls={rep['host_calls']};device_calls={rep['device_calls']}")


def kernel_bench(grid=(64, 64, 64), reps: int = 20):
    """Solver hot-spot micro-bench: jnp reference timings + the fused
    kernel's analytic HBM-traffic ratio (the kernel itself runs in
    interpret mode on CPU, so its wall-time is not meaningful here)."""
    from repro.cfd import fvm
    from repro.cfd.dia import DiaMatrix, amul_ref
    from repro.cfd.grid import Grid
    from repro.cfd.precond import RBDilu, rb_dilu_apply, rb_dilu_factor
    g = Grid(grid)
    A, _ = fvm.laplacian(g, 1.0)
    x = jnp.ones(g.shape, jnp.float32)
    f = jax.jit(lambda d, o, x: amul_ref(DiaMatrix(d, o), x))
    f(A.diag, A.off, x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        y = f(A.diag, A.off, x)
    y.block_until_ready()
    row("kernel/amul_jnp", (time.perf_counter() - t0) / reps * 1e6,
        f"cells={g.n}")
    # per-cell float traffic: unfused = 7 shifted passes (read+write each)
    # + 7 coeff reads + 1 write; fused = x(+halo) + 7 coeffs + 1 write
    row("kernel/amul_traffic_ratio", 0.0, f"x{(7 * 2 + 7 + 1) / 10:.2f}")
    red, _ = g.red_black_masks()
    P = rb_dilu_factor(A, red)
    h = jax.jit(lambda rd, r: rb_dilu_apply(RBDilu(rd, red), A, r))
    h(P.rdiag, x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        y = h(P.rdiag, x)
    y.block_until_ready()
    row("kernel/rb_dilu_jnp", (time.perf_counter() - t0) / reps * 1e6,
        f"cells={g.n}")


def solver_bench(grid=(32, 32, 32)):
    """PBiCGStab end-to-end: region-granular (paper) vs fused while_loop
    (beyond-paper) on identical systems."""
    from repro.cfd import fvm
    from repro.cfd.grid import Grid
    from repro.cfd.precond import rb_dilu_factor
    from repro.cfd.solvers import (make_solver_regions, pbicgstab_fused,
                                   pbicgstab_regions)
    from repro.core.ledger import Ledger
    from repro.core.regions import Executor, UnifiedPolicy
    g = Grid(grid)
    A, _ = fvm.laplacian(g, 1.0)
    b = jnp.ones(g.shape, jnp.float32)
    red, _ = g.red_black_masks()
    P = rb_dilu_factor(A, red)
    ldg = Ledger("bench")
    regions = make_solver_regions(ldg)
    ex = Executor(UnifiedPolicy(), ldg)
    pbicgstab_regions(ex, regions, A, b, jnp.zeros_like(b), P, tol=1e-6)
    t0 = time.perf_counter()
    r = pbicgstab_regions(ex, regions, A, b, jnp.zeros_like(b), P, tol=1e-6)
    t_reg = time.perf_counter() - t0
    pbicgstab_fused(A, b, jnp.zeros_like(b), P.rdiag, P.red, tol=1e-6)
    t0 = time.perf_counter()
    x, it, _, res = pbicgstab_fused(A, b, jnp.zeros_like(b), P.rdiag, P.red,
                                    tol=1e-6)
    jax.block_until_ready(x)
    t_fused = time.perf_counter() - t0
    row("solver/pbicgstab_regions", t_reg * 1e6, f"iters={r.iters}")
    row("solver/pbicgstab_fused", t_fused * 1e6,
        f"iters={int(it)};speedup=x{t_reg / max(t_fused, 1e-12):.2f}")


def lm_train_bench(steps: int = 3):
    """LM substrate throughput at smoke scale (tok/s, reduced tinyllama)."""
    from repro.launch.train import main
    t0 = time.perf_counter()
    losses = main(["--arch", "tinyllama-1.1b", "--reduced",
                   "--steps", str(steps), "--batch", "4", "--seq", "64"])
    dt = (time.perf_counter() - t0) / steps
    row("lm/train_step_reduced", dt * 1e6, f"loss={losses[-1]:.3f}")


def roofline_report(art_dir: str = "artifacts/dryrun"):
    """Summarize the dry-run roofline artifacts (docs/EXPERIMENTS.md source)."""
    d = Path(art_dir)
    if not d.exists():
        row("roofline/missing", 0.0, "run launch.dryrun --sweep first")
        return
    cells = []
    for f in sorted(d.glob("*__sp.json")):
        rec = json.loads(f.read_text())
        if rec.get("status") != "ok":
            continue
        r = rec["roofline"]
        cells.append((rec["arch"], rec["shape"], r["bottleneck"],
                      r["roofline_fraction"]))
        row(f"roofline/{rec['arch']}/{rec['shape']}",
            max(r["compute_s"], r["memory_s"], r["collective_s"]) * 1e6,
            f"bottleneck={r['bottleneck'].replace('_s', '')}"
            f";fraction={r['roofline_fraction']:.4f}")
    if cells:
        worst = min(cells, key=lambda c: c[3])
        row("roofline/worst_cell", 0.0,
            f"{worst[0]}/{worst[1]};fraction={worst[3]:.5f}")


BENCHES = {
    "fig5_speedup": fig5_speedup,
    "fig6_migration": fig6_migration,
    "fig6b_overlap": fig6b_overlap,
    "fig_scaling": fig_scaling,
    "fig_variants": fig_variants,
    "fig4_coverage": fig4_coverage,
    "fig_serve": fig_serve,
    "fig_traffic": fig_traffic,
    "fig_oversub": fig_oversub,
    "fig_tune": fig_tune,
    "pool": pool_bench,
    "dispatch": dispatch_bench,
    "kernel": kernel_bench,
    "solver": solver_bench,
    "lm_train": lm_train_bench,
    "roofline": roofline_report,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help=f"comma list of benchmarks ({','.join(BENCHES)})")
    ap.add_argument("--out", default="",
                    help="also write the CSV rows to this file")
    args = ap.parse_args(argv)
    names = [n for n in args.only.split(",") if n] or list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        raise SystemExit(f"unknown benchmark(s): {unknown}")
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n]()
    if args.out:
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text("name,us_per_call,derived\n" + "".join(
            f"{n},{us:.1f},{d}\n" for n, us, d in ROWS))
        print(f"[bench] wrote {len(ROWS)} rows to {out}", flush=True)


if __name__ == "__main__":
    main()
