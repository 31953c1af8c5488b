"""End-to-end training driver — on the region-program spine.

Integrates the full stack: config registry (--arch, full or --reduced),
mesh + logical-axis sharding (FSDP/TP), the region-decomposed train step
(``FWD_BWD`` + ``ADAMW_UPDATE`` Regions captured as one RegionProgram and
replayed through an Executor under ``--policy``), the unified-memory
placement axis (--offload-optimizer attaches a host-space hint to the
AdamW moments — paper C1, no hand-rolled placement calls), pooled host
staging, async atomic checkpointing (each checkpoint carries a
``coverage_report()`` snapshot beside the weights), the fault-tolerant
supervisor (restarts re-capture the program against restored state while
keeping the same Ledger), and the deterministic data pipeline.
``--report`` prints the canonical ``coverage_report()`` as JSON.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --steps 50 --batch 8 --seq 64
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-moe-30b-a3b \
      --reduced --steps 20 --batch 4 --seq 32 --offload-optimizer --report
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import Checkpointer
from repro.configs.base import ModelConfig
from repro.configs.reduced import reduced as make_reduced
from repro.configs.registry import get_config
from repro.core.ledger import Ledger
from repro.core.regions import Executor
from repro.core.umem import place_like
from repro.data.pipeline import ShardInfo, make_source
from repro.launch.compilation import configure_compilation
from repro.launch import sharding as SH
from repro.launch.mesh import make_smoke_mesh
from repro.launch.policy import POLICY_CHOICES, lm_policy
from repro.models import transformer as T
from repro.models.params import abstract_params
from repro.optim import adamw
from repro.runtime.fault import FaultInjector, StragglerMonitor, TrainSupervisor
from repro.train import step as S


def build_trainer(cfg: ModelConfig, mesh, *, lr=3e-4, offload_optimizer=False,
                  q_chunk=512, seed=0, policy: str = "unified",
                  executor: Optional[Executor] = None,
                  verify: bool = False, tuned_size: Optional[int] = None):
    """Returns ``(init_fn, capture_fn, ex)``.

    ``init_fn() -> state`` builds sharded params + optimizer state.
    ``capture_fn(state, batch) -> step_fn`` captures one train step as a
    RegionProgram over the trainer's ``FWD_BWD``/``ADAMW_UPDATE`` regions
    and returns ``step_fn(state, batch) -> (state, metrics)`` replaying it
    through ``ex`` — call it again after a restore to re-capture (the
    regions, and therefore the Ledger rows, are reused).
    ``ex`` is the Executor every step runs under; ``ex.report()`` is the
    canonical coverage report for the run.  The Executor waits only for
    staged calls, so the report's compute seconds (and the fractions
    built on them) of the other calls are the host's dispatch time: its
    ``syncs`` says how many calls were waited for.

    Memory note: the pre-regions trainer jitted the whole step with
    ``donate_argnums=(0,)``, updating params/moments in place.  Region
    executables do not donate (a replayed region may be staged, and the
    discrete stager recycles staged-in buffers after the call — donation
    would hand consumed storage back to the pool), so peak state memory
    is roughly 2x the old path at the ADAMW_UPDATE boundary.  A
    stage-aware donation axis is the natural follow-up; at the smoke
    scales this container runs, the 2x is noise.
    """
    rules = SH.ShardingRules("train")
    shd = SH.make_sharder(mesh, rules)
    opt_cfg = adamw.AdamWConfig(lr=lr)
    specs = T.param_specs(cfg)
    psh = SH.tree_param_shardings(specs, mesh, rules)

    if executor is not None:
        ex = executor
    elif policy == "auto":
        # tuned warm-start: profile's train_step winner at this workload
        # size (``repro.tune.space.train_size``); with no ``tuned_size``
        # the nearest calibrated bucket still resolves (lazy import —
        # repro.tune's workload harness imports this driver back)
        from repro.core.program import AsyncExecutor
        from repro.launch.policy import auto_policy
        pol = auto_policy("train_step", tuned_size or 0, cfg.memory)
        entry = getattr(pol, "tuned_entry", None)
        led = Ledger("train")
        ex = (AsyncExecutor(pol, led)
              if entry is not None and entry.candidate.staging == "async"
              else Executor(pol, led))
    else:
        ex = Executor(lm_policy(policy, cfg.memory), Ledger("train"))
    make_ctx = lambda: T.Ctx(mode="train", shd=shd, q_chunk=q_chunk)
    regions = S.make_train_regions(cfg, opt_cfg, make_ctx, ledger=ex.ledger,
                                   offload_optimizer=offload_optimizer)

    def init_fn():
        key = jax.random.PRNGKey(seed)
        params = jax.jit(lambda k: T.init(k, cfg), out_shardings=psh)(key)
        # moments mirror their params' FSDP/TP partitioning (a moment tree
        # left unsharded would clash with mesh-committed params inside the
        # ADAMW_UPDATE jit on any real mesh); which memory SPACE they live
        # in stays a policy-axis decision — the ADAMW_UPDATE placement
        # hints move them to host space when --offload-optimizer is set
        opt = adamw.init_state(params, opt_cfg)
        opt = {"m": place_like(opt["m"], psh),
               "v": place_like(opt["v"], psh),
               "step": opt["step"]}
        return (params, opt)

    def capture_fn(state, batch):
        prog = S.capture_train_program(regions, state, batch)
        if verify:
            # --verify: lint the fresh FWD_BWD + ADAMW_UPDATE trace under
            # the training policy before the first replay (repro.analysis;
            # supervisor re-captures re-verify the same way)
            rep = prog.verify(ex.policy, ledger=ex.ledger)
            print(f"[verify] {rep.summary()}")
            for d in rep.findings:
                print(f"    {d}")
            if rep.errors:
                raise SystemExit(f"[verify] {prog.name!r} has "
                                 "error-severity findings; refusing to "
                                 "train")

        def step_fn(state, batch):
            return prog.replay(ex, state, batch)

        return step_fn

    return init_fn, capture_fn, ex


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--offload-optimizer", action="store_true")
    ap.add_argument("--policy", default="unified", choices=POLICY_CHOICES,
                    help="ExecutionPolicy the train-step regions run under "
                         "(adaptive threads cfg.memory.target_cutoff)")
    ap.add_argument("--verify", action="store_true",
                    help="statically lint the captured train-step program "
                         "(FWD_BWD + ADAMW_UPDATE) under the training "
                         "policy at capture; error-severity findings "
                         "abort (repro.analysis, docs/ANALYSIS.md)")
    ap.add_argument("--report", action="store_true",
                    help="print the run's coverage_report() as JSON (the "
                         "compute seconds of calls not waited for, all "
                         "but its 'syncs', are host dispatch time)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--data", default="synthetic")
    ap.add_argument("--data-path", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fail-at", default="", help="fault injection steps, csv")
    args = ap.parse_args(argv)
    configure_compilation()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    mesh = make_smoke_mesh()
    tuned_size = None
    if args.policy == "auto":
        from repro.tune.space import train_size
        tuned_size = train_size(args.batch, args.seq, cfg.d_model)
    init_fn, capture_fn, ex = build_trainer(
        cfg, mesh, lr=args.lr, offload_optimizer=args.offload_optimizer,
        q_chunk=min(512, args.seq), seed=args.seed, policy=args.policy,
        verify=args.verify, tuned_size=tuned_size)
    src = make_source(args.data, cfg.vocab, path=args.data_path,
                      seed=args.seed)

    def batch_fn(step):
        tok = jnp.asarray(src.batch_at(step, args.batch, args.seq))
        b = {"tokens": tok}
        if cfg.mrope_sections is not None:
            pos = jnp.arange(args.seq, dtype=jnp.int32)[None, :, None]
            b["positions3"] = jnp.broadcast_to(pos, (args.batch, args.seq, 3))
        if cfg.n_enc_layers:
            key = jax.random.PRNGKey(step)
            b["enc_embeds"] = jax.random.normal(
                key, (args.batch, cfg.enc_len, cfg.d_model),
                jnp.float32).astype(cfg.compute_dtype)
        return b

    state = init_fn()
    start = 0
    ckpt = None
    if args.ckpt_dir:
        ckpt = Checkpointer(args.ckpt_dir, keep=3)
        if args.resume and ckpt.latest_step() is not None:
            state, man = ckpt.restore(state)
            start = man["extra"]["step"]
            print(f"[train] resumed at step {start}")

    step_fn = capture_fn(state, batch_fn(start))
    t0 = time.time()
    if ckpt is not None:
        fault = FaultInjector({int(s) for s in args.fail_at.split(",") if s})
        sup = TrainSupervisor(
            step_fn, batch_fn, ckpt, ckpt_every=args.ckpt_every, fault=fault,
            rebuild_step=lambda st, step: capture_fn(st, batch_fn(step)),
            report_fn=ex.report)
        state, rep = sup.run(state, start, args.steps)
        print(f"[train] done: {rep}")
        losses = [rep.metrics_last.get("loss", float("nan"))]
    else:
        losses = []
        for step in range(start, start + args.steps):
            state, metrics = step_fn(state, batch_fn(step))
            losses.append(float(metrics["loss"]))
            if step % 10 == 0 or step == start + args.steps - 1:
                print(f"[train] step {step} loss {losses[-1]:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f}")
    dt = time.time() - t0
    toks = args.steps * args.batch * args.seq
    print(f"[train] {args.arch}{' (reduced)' if args.reduced else ''}: "
          f"{toks/dt:.0f} tok/s, first loss {losses[0]:.4f}, "
          f"last loss {losses[-1]:.4f}")
    if args.report:
        print(json.dumps(ex.report(), indent=1, default=str))
    return losses


if __name__ == "__main__":
    main()
