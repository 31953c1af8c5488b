"""Tunable workloads: each wraps one shipped captured program.

A :class:`Workload` gives the tuner everything it needs for one search:
the captured :class:`~repro.core.program.RegionProgram` (cost-model
input), a ``run(candidate, steps)`` measurement that replays it under
the candidate's policy and returns parity leaves + a FOM + per-region
measured seconds (residual calibration), the hand-assembled reference
candidate the winner must beat, and the workload-shape ``size`` that
keys the profile bucket.

The four registered workloads mirror the ``fig_tune`` benchmark:

* ``cfd_step`` — the captured SIMPLE step (smoke grid); ref is the
  managed-dGPU ``discrete`` baseline (paper Figs 5/6).
* ``serve_decode`` — the serve DECODE_STEP+KV_APPEND program at the
  analysis-corpus smoke shape; ref ``discrete``.
* ``train_step`` — the FWD_BWD+ADAMW_UPDATE step; ref ``discrete``.
* ``cfd_sharded`` — the SIMPLE step decomposed over several devices via
  ``repro.launch.scaling.run`` (in this process on real chips, in a
  subprocess on forced CPU devices, whose count must be in XLA_FLAGS
  before jax imports); ref is the sequential 1-D slab schedule (the PR-3
  baseline).

Contexts are built once per process (capture is the expensive part) and
cached, the same trick as ``repro.analysis.programs``.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from repro.core.ledger import Ledger
from repro.core.regions import Executor, Placer, UnifiedPolicy
from repro.launch import scaling
from repro.tune.space import PolicyCandidate, cfd_size, serve_size, train_size

#: serve/train smoke shapes (mirror repro.analysis.programs)
BATCH, PROMPT, GEN = 2, 8, 4
MAX_LEN = PROMPT + GEN

#: CFD smoke shapes
CFD_GRID = (12, 12, 12)
CFD_INNER = 6
SHARD_GRID = (8, 8, 8)
SHARD_INNER = 4

#: simulated APU count the sharded workload decomposes over
SHARD_APUS = int(os.environ.get("REPRO_TUNE_APUS", "4"))

#: placement hints skip leaves below this (mirrors launch.policy)
_PLACER_MIN_BYTES = 4096


@dataclasses.dataclass
class RunResult:
    """One measured replay: parity leaves, FOM, per-region seconds."""
    leaves: List[np.ndarray]
    fom_s: float
    region_s: Dict[str, float]
    replays: int = 1                 # program replays the window covered
    extra: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Workload:
    """One tunable workload (see module docstring)."""
    name: str
    kind: str                        # "replay" | "sharded"
    size: int                        # bucket key (see space.*_size)
    memory: Any                      # MemoryPolicy for cutoff defaults
    build_program: Callable[[], Any]
    run: Callable[..., RunResult]    # (candidate, steps, winners=) -> RunResult
    ref: PolicyCandidate
    steps: int = 2                   # default measured replays
    meta: dict = dataclasses.field(default_factory=dict)


def _executor(candidate: PolicyCandidate, memory, winners, name: str):
    """Executor (or AsyncExecutor, for async-staging candidates) running
    the candidate's concrete policy."""
    from repro.core.program import AsyncExecutor
    pol = candidate.build_policy(memory, winners=winners,
                                 placer=Placer(min_bytes=_PLACER_MIN_BYTES))
    cls = AsyncExecutor if candidate.staging == "async" else Executor
    return cls(pol, Ledger(name))


def _region_seconds(ledger: Ledger) -> Dict[str, float]:
    return {name: row.compute_s for name, row in ledger.regions.items()
            if row.compute_s > 0}


# ---------------------------------------------------------------------------
# cfd_step
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _cfd_ctx():
    from repro.cfd.grid import Grid
    from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
    cfg = SimpleConfig(grid=Grid(CFD_GRID), nu=0.1, inner_max=CFD_INNER)
    app = SimpleFoam(cfg)
    st = init_state(cfg)
    st, _, _ = app.run_steps(st, 1)          # develop flow + warm caches
    return app, st, app.capture_step(st)


def _run_cfd(candidate: PolicyCandidate, steps: int,
             winners=None) -> RunResult:
    app, st, prog = _cfd_ctx()
    ex = _executor(candidate, None, winners, f"tune_cfd_{candidate.label}")
    app.replay_steps(prog, st, 1, ex)        # warm per-target compiles
    ex.ledger.reset_timings()
    s, fom = app.replay_steps(prog, st, steps, ex)
    leaves = [np.asarray(f) for f in (s.u, s.v, s.w, s.p)]
    return RunResult(leaves, fom, _region_seconds(ex.ledger), replays=steps)


# ---------------------------------------------------------------------------
# serve_decode
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _serve_ctx():
    import jax
    import jax.numpy as jnp

    from repro.configs.reduced import reduced as make_reduced
    from repro.configs.registry import get_config
    from repro.launch import serve as SV
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import transformer as T

    cfg = make_reduced(get_config("tinyllama-1.1b"))
    mesh = make_smoke_mesh()
    key = jax.random.PRNGKey(0)
    params = T.init(key, cfg)
    prompts = jax.random.randint(key, (BATCH, PROMPT), 0, cfg.vocab,
                                 jnp.int32)
    batch_in = {"tokens": prompts}
    regions = SV.make_serve_regions(cfg, mesh, params,
                                    ledger=Ledger("tune_serve"))
    prefill_prog = SV.capture_prefill_program(
        regions, batch_in, T.init_cache(cfg, BATCH, MAX_LEN))
    warm = Executor(UnifiedPolicy(), Ledger("tune_serve_warm"))
    tok, cache = prefill_prog.replay(warm, batch_in,
                                     T.init_cache(cfg, BATCH, MAX_LEN))
    decode_prog = SV.capture_decode_program(regions, PROMPT, GEN, tok, cache)
    return cfg, batch_in, prefill_prog, decode_prog


def _run_serve(candidate: PolicyCandidate, steps: int,
               winners=None) -> RunResult:
    import jax.numpy as jnp

    from repro.models import transformer as T
    cfg, batch_in, prefill_prog, decode_prog = _serve_ctx()
    warm = Executor(UnifiedPolicy(), Ledger("tune_serve_prefill"))
    tok, cache = prefill_prog.replay(warm, batch_in,
                                     T.init_cache(cfg, BATCH, MAX_LEN))
    ex = _executor(candidate, cfg.memory, winners,
                   f"tune_serve_{candidate.label}")
    decode_prog.replay(ex, tok, cache)       # warm per-target compiles
    ex.ledger.reset_timings()
    t0 = time.perf_counter()
    for _ in range(steps):
        toks = decode_prog.replay(ex, tok, cache)
    fom = (time.perf_counter() - t0) / (steps * max(GEN - 1, 1))
    leaves = [np.asarray(jnp.stack(toks, axis=1))]
    return RunResult(leaves, fom, _region_seconds(ex.ledger), replays=steps)


# ---------------------------------------------------------------------------
# train_step
# ---------------------------------------------------------------------------

TRAIN_BATCH, TRAIN_SEQ = 2, 16


@functools.lru_cache(maxsize=None)
def _train_ctx():
    import jax
    import jax.numpy as jnp

    from repro.configs.reduced import reduced as make_reduced
    from repro.configs.registry import get_config
    from repro.models import transformer as T
    from repro.optim import adamw
    from repro.train import step as S

    cfg = make_reduced(get_config("tinyllama-1.1b"))
    opt_cfg = adamw.AdamWConfig(lr=1e-3)
    key = jax.random.PRNGKey(1)
    params = T.init(key, cfg)
    opt = adamw.init_state(params, opt_cfg)
    batch = {"tokens": jax.random.randint(key, (TRAIN_BATCH, TRAIN_SEQ), 0,
                                          cfg.vocab, jnp.int32)}
    regions = S.make_train_regions(cfg, opt_cfg, ledger=Ledger("tune_train"))
    prog = S.capture_train_program(regions, (params, opt), batch)
    return cfg, (params, opt), batch, prog


def _run_train(candidate: PolicyCandidate, steps: int,
               winners=None) -> RunResult:
    import jax
    cfg, state0, batch, prog = _train_ctx()
    ex = _executor(candidate, cfg.memory, winners,
                   f"tune_train_{candidate.label}")
    prog.replay(ex, state0, batch)           # warm per-target compiles
    ex.ledger.reset_timings()
    state, metrics = state0, {}
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = prog.replay(ex, state, batch)
    fom = (time.perf_counter() - t0) / steps
    leaves = [np.asarray(metrics["loss"]), np.asarray(metrics["grad_norm"])]
    leaves += [np.asarray(x) for x in jax.tree.leaves(state)[:2]]
    return RunResult(leaves, fom, _region_seconds(ex.ledger), replays=steps)


# ---------------------------------------------------------------------------
# cfd_sharded (repro.launch.scaling.run: a subprocess on forced CPU
# devices, where the APU count must precede the jax import)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _sharded_prog():
    from repro.cfd.grid import Grid
    from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
    cfg = SimpleConfig(grid=Grid(SHARD_GRID), nu=0.1, inner_max=SHARD_INNER)
    app = SimpleFoam(cfg)
    st = init_state(cfg)
    st, _, _ = app.run_steps(st, 1)
    return app.capture_step(st)


def _run_sharded(candidate: PolicyCandidate, steps: int,
                 winners=None) -> RunResult:
    mesh = candidate.mesh or (SHARD_APUS,)
    apus = 1
    for s in mesh:
        apus *= s
    argv = ["--apus", str(apus),
            "--mesh", "x".join(str(s) for s in mesh),
            "--steps", str(steps),
            "--grid", ",".join(str(g) for g in SHARD_GRID),
            "--policy", candidate.placement,
            "--schedule", candidate.schedule,
            "--halo-multiplier", str(candidate.halo_multiplier),
            "--inner-max", str(SHARD_INNER)]
    with tempfile.TemporaryDirectory() as td:
        try:
            rec = scaling.run(argv, Path(td) / "run.json")
        except RuntimeError as e:
            raise RuntimeError(f"sharded measurement failed for "
                               f"{candidate.label}") from e
    if not rec["parity_ok"]:                 # DESIGN §2, asserted in-run too
        raise AssertionError(f"{candidate.label}: sharded replay lost "
                             f"parity: {rec['parity_max_abs_err']:.2e}")
    extra = {k: rec[k] for k in ("exchange_fraction", "exchange_s",
                                 "overlap_s", "mesh_shape", "schedule")}
    return RunResult([], rec["fom_sharded_s"], {}, replays=steps,
                     extra=extra)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def _serve_workload() -> Workload:
    # reduced tinyllama d_model = 64 at the corpus smoke shape; build the
    # size without importing jax-heavy context (the driver-side formula)
    from repro.configs.reduced import reduced as make_reduced
    from repro.configs.registry import get_config
    cfg = make_reduced(get_config("tinyllama-1.1b"))
    return Workload(
        name="serve_decode", kind="replay",
        size=serve_size(BATCH, MAX_LEN, cfg.d_model), memory=cfg.memory,
        build_program=lambda: _serve_ctx()[3], run=_run_serve,
        ref=PolicyCandidate(placement="discrete"), steps=2,
        meta={"batch": BATCH, "prompt": PROMPT, "gen": GEN})


def _train_workload() -> Workload:
    from repro.configs.reduced import reduced as make_reduced
    from repro.configs.registry import get_config
    cfg = make_reduced(get_config("tinyllama-1.1b"))
    return Workload(
        name="train_step", kind="replay",
        size=train_size(TRAIN_BATCH, TRAIN_SEQ, cfg.d_model),
        memory=cfg.memory,
        build_program=lambda: _train_ctx()[3], run=_run_train,
        ref=PolicyCandidate(placement="discrete"), steps=2,
        meta={"batch": TRAIN_BATCH, "seq": TRAIN_SEQ})


def _cfd_workload() -> Workload:
    return Workload(
        name="cfd_step", kind="replay", size=cfd_size(CFD_GRID), memory=None,
        build_program=lambda: _cfd_ctx()[2], run=_run_cfd,
        ref=PolicyCandidate(placement="discrete"), steps=2,
        meta={"grid": CFD_GRID})


def _sharded_workload() -> Workload:
    return Workload(
        name="cfd_sharded", kind="sharded", size=cfd_size(SHARD_GRID),
        memory=None, build_program=_sharded_prog, run=_run_sharded,
        ref=PolicyCandidate(placement="unified", schedule="sequential",
                            halo_multiplier=1, mesh=(SHARD_APUS,)),
        steps=1, meta={"grid": SHARD_GRID, "apus": SHARD_APUS})


_REGISTRY: Dict[str, Callable[[], Workload]] = {
    "cfd_step": _cfd_workload,
    "serve_decode": _serve_workload,
    "train_step": _train_workload,
    "cfd_sharded": _sharded_workload,
}

WORKLOAD_NAMES = tuple(_REGISTRY)


def get_workload(name: str) -> Workload:
    if name not in _REGISTRY:
        raise KeyError(f"unknown workload {name!r}; "
                       f"available: {WORKLOAD_NAMES}")
    return _REGISTRY[name]()
