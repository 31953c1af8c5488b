"""The search: score the whole space from the cost model, measure finalists.

Three stages per workload (docs/AUTOTUNE.md):

1. **Profile once** — run the PR-9 roofline bridge
   (``repro.analysis.costs``) over the workload's captured program, then
   take ONE measured calibration replay of the reference candidate and
   store the per-region model-vs-measured residual (``measured /
   modeled``).  The residuals correct the model where the container
   diverges from the MI300A roofline; they persist in the profile so a
   later search can warm-start without re-measuring.
2. **Search** — score every :func:`~repro.tune.space.enumerate_candidates`
   point: residual-corrected roofline seconds plus placement priors —
   the discrete staging tax priced at asymmetric host<->device
   bandwidth fractions (seeded from the measured UPM asymmetries in
   "Dissecting CPU-GPU Unified Physical Memory on AMD MI300A APUs",
   PAPERS.md), a host-compute slowdown, an async-overlap discount, and
   for sharded workloads a halo-exchange surface/sync model over the
   mesh-shape x schedule x halo axes.
3. **Measure finalists** — the top-scored candidates (placement/staging
   diversity first) get short measured replays, each parity-asserted
   against the reference leaves (DESIGN §2 tolerance); the winner is
   the best measured FOM among finalists AND the reference, so a tuned
   profile can never elect a candidate that measured worse than the
   hand-assembled baseline it was searched against.

``trials=0`` skips all measurement (given precomputed residuals) and
elects the best-scored candidate — the deterministic pure-model path the
tests pin: same inputs, same winners.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.core.regions import DEFAULT_CUTOFF, size_bucket
from repro.tune.profile import PolicyProfile, ProfileEntry
from repro.tune.space import PolicyCandidate, enumerate_candidates
from repro.tune.workloads import RunResult, Workload, get_workload

# -- placement priors -------------------------------------------------------
# Seeded from the measured MI300A UPM bandwidth asymmetries ("Dissecting
# CPU-GPU Unified Physical Memory on AMD MI300A APUs", PAPERS.md): unified
# fine-grained access runs near HBM rate on-package, while the managed /
# discrete path pays staged copies at a fraction of HBM bandwidth — and
# asymmetrically, with device->host writeback the slower direction.  The
# absolute fractions only have to get the *ranking* right; the measured
# finalist pass owns the final ordering.
H2D_BW_FRACTION = 0.30      # stage-in bandwidth as a fraction of HBM_BW
D2H_BW_FRACTION = 0.20      # stage-out (writeback) — the slow side
HOST_COMPUTE_FACTOR = 8.0   # host-routed region slowdown vs device roofline
ASYNC_OVERLAP_PRIOR = 0.6   # staging fraction the lookahead hides (fig6b)

# sharded exchange model (docs/SCALING.md cost structure)
EXCHANGE_BW_FRACTION = 0.5  # inter-APU fabric vs HBM bandwidth
SYNC_LATENCY_S = 5e-5       # per halo-exchange rendezvous
STENCIL_APPS_PRIOR = 24.0   # stencil applications per step (halo syncs)
FIELDS_PRIOR = 8            # arrays exchanged per stencil application
SCHEDULE_EXPOSURE = {"sequential": 1.0, "split": 0.6, "overlap": 0.35}

#: DESIGN §2 float replay-parity tolerance
PARITY_RTOL = 1e-5


def model_costs(prog) -> dict:
    """Aggregate the per-op roofline estimates into what scoring needs:
    per-region seconds/bytes plus a flat op list (region, roofline_s,
    hbm_bytes) for routing-cutoff modeling."""
    from repro.analysis.costs import estimate_program_costs
    est = estimate_program_costs(prog)
    region_s: Dict[str, float] = {}
    region_bytes: Dict[str, int] = {}
    ops = []
    for o in est["ops"]:
        t = max(o["roofline_compute_s"], o["roofline_memory_s"])
        region_s[o["region"]] = region_s.get(o["region"], 0.0) + t
        region_bytes[o["region"]] = (region_bytes.get(o["region"], 0)
                                     + o["hbm_bytes"])
        ops.append((o["region"], t, o["hbm_bytes"]))
    return {"region_s": region_s, "region_bytes": region_bytes, "ops": ops,
            "total_s": sum(region_s.values()),
            "total_bytes": sum(region_bytes.values()),
            "skipped": est["skipped"]}


def compute_residuals(model: dict, measured_region_s: Dict[str, float],
                      replays: int = 1) -> Dict[str, float]:
    """Per-region ``measured / modeled`` correction factors from one
    calibration replay, plus the ``"*"`` global fallback for regions the
    model skipped or the ledger renamed."""
    res: Dict[str, float] = {}
    matched_meas = matched_model = 0.0
    for name, modeled in model["region_s"].items():
        meas = measured_region_s.get(name)
        if meas is None or modeled <= 0:
            continue
        res[name] = meas / (modeled * max(replays, 1))
        matched_meas += meas
        matched_model += modeled * max(replays, 1)
    res["*"] = (matched_meas / matched_model) if matched_model > 0 else 1.0
    return res


def _roofline_bw() -> float:
    from repro.analysis.costs import _roofline_constants
    return _roofline_constants()[1]


def score_candidate(candidate: PolicyCandidate, model: dict,
                    residuals: Optional[Dict[str, float]] = None,
                    kind: str = "replay", meta: Optional[dict] = None,
                    hbm_bw: Optional[float] = None) -> float:
    """Predicted seconds per program replay for ``candidate`` — the
    pruning score.  Selector choices score identically (the roofline
    cannot see implementation quality); they are separated by the
    measured finalist pass, with ties resolved by candidate order."""
    residuals = residuals or {}
    glob = residuals.get("*", 1.0)
    hbm_bw = hbm_bw or _roofline_bw()
    cutoff = candidate.cutoff or DEFAULT_CUTOFF
    total = 0.0
    for region, t, nbytes in model["ops"]:
        t = t * residuals.get(region, glob)
        if candidate.placement == "host":
            t *= HOST_COMPUTE_FACTOR
        elif candidate.placement == "adaptive":
            # SizeRouter sends small calls to the host; approximate the
            # call's element count from its modeled f32 traffic
            if nbytes // 12 < cutoff:
                t *= HOST_COMPUTE_FACTOR
        total += t
    if candidate.placement == "discrete":
        staging = model["total_bytes"] * (1.0 / (H2D_BW_FRACTION * hbm_bw)
                                          + 1.0 / (D2H_BW_FRACTION * hbm_bw))
        if candidate.staging == "async":
            staging *= 1.0 - ASYNC_OVERLAP_PRIOR
        total += staging
    if kind == "sharded" and candidate.mesh is not None:
        total += _exchange_model(candidate, (meta or {}).get("grid"),
                                 hbm_bw)
    return total


def _exchange_model(candidate: PolicyCandidate, grid, hbm_bw: float) -> float:
    """Exposed halo-exchange seconds per step: surface bytes over the
    fabric plus per-sync latency, discounted by the schedule's exposure
    and the wide-halo sync reduction.  Mesh axes map to trailing grid
    dims (the ShardExecutor convention)."""
    if not grid:
        return 0.0
    mesh = candidate.mesh
    halo = max(candidate.halo_multiplier, 1)
    cells = 1
    for g in grid:
        cells *= int(g)
    surface_cells = 0
    for dim, m in zip(range(-len(mesh), 0), mesh):
        if m <= 1:
            continue
        plane = cells // int(grid[dim])          # cells in one cut plane
        surface_cells += 2 * halo * plane * (m - 1)
    n_syncs = STENCIL_APPS_PRIOR / halo
    xbytes = surface_cells * 4 * FIELDS_PRIOR * n_syncs
    exposure = SCHEDULE_EXPOSURE.get(candidate.schedule, 1.0)
    return (xbytes / (EXCHANGE_BW_FRACTION * hbm_bw)
            + SYNC_LATENCY_S * n_syncs) * exposure


def check_parity(leaves: List[np.ndarray], ref: List[np.ndarray],
                 rtol: float = PARITY_RTOL) -> float:
    """Max abs error of ``leaves`` vs ``ref`` under the DESIGN §2
    contract — integer leaves must match bit-for-bit, float leaves
    within ``rtol`` of the reference scale.  Raises AssertionError."""
    worst = 0.0
    assert len(leaves) == len(ref), (len(leaves), len(ref))
    for a, b in zip(leaves, ref):
        a, b = np.asarray(a), np.asarray(b)
        if np.issubdtype(b.dtype, np.integer) or b.dtype == np.bool_:
            np.testing.assert_array_equal(a, b)
            continue
        scale = max(1.0, float(np.max(np.abs(b))) if b.size else 1.0)
        err = float(np.max(np.abs(a - b))) if b.size else 0.0
        assert err <= rtol * scale, (err, rtol * scale)
        worst = max(worst, err)
    return worst


@dataclasses.dataclass
class TuneResult:
    """One workload's search outcome (feeds a ProfileEntry)."""
    workload: str
    bucket: int
    winner: PolicyCandidate
    fom_s: Optional[float]
    ref_fom_s: Optional[float]
    score_s: float
    residuals: Dict[str, float]
    table: List[dict]                # every candidate: label/score/fom
    disqualified: List[str] = dataclasses.field(default_factory=list)

    def to_entry(self, variant_winners=None) -> ProfileEntry:
        return ProfileEntry(
            workload=self.workload, bucket=self.bucket,
            candidate=self.winner, fom_s=self.fom_s,
            ref_fom_s=self.ref_fom_s, score_s=self.score_s,
            residuals=dict(self.residuals),
            variant_winners=dict(variant_winners or {})
            if self.winner.selector == "autotuned" else {})


def _diverse_finalists(scored: List[tuple], trials: int) -> List[int]:
    """Indices of the measured finalists: best score per new
    (placement, staging) pair first — so the measured pass always sees
    placement diversity — then remaining slots in pure score order."""
    picked: List[int] = []
    seen = set()
    for _, i, cand in scored:
        key = (cand.placement, cand.staging)
        if key not in seen:
            seen.add(key)
            picked.append(i)
        if len(picked) >= trials:
            return picked
    for _, i, _cand in scored:
        if i not in picked:
            picked.append(i)
        if len(picked) >= trials:
            break
    return picked


def tune(workload: Workload, *, trials: int = 3, steps: Optional[int] = None,
         winners: Optional[Dict[str, str]] = None,
         residuals: Optional[Dict[str, float]] = None,
         measure: Optional[Callable] = None, seed: int = 0) -> TuneResult:
    """Search one workload (module docstring).  ``measure(workload,
    candidate, steps) -> RunResult`` is injectable for deterministic
    tests; ``residuals`` warm-starts calibration (required when
    ``trials=0`` wants a fully measurement-free run).  ``seed`` is
    recorded for forward compatibility — the search itself is
    deterministic by construction (fixed enumeration order, score ties
    resolve to the earlier candidate)."""
    del seed  # deterministic search: nothing random to seed (yet)
    steps = steps or workload.steps
    if measure is None:
        def measure(w, c, s):
            # only the reference's region seconds calibrate the residuals
            return w.run(c, s, winners=winners, calibrate=c == w.ref)
    model = model_costs(workload.build_program())

    ref_res: Optional[RunResult] = None
    if residuals is None:
        ref_res = measure(workload, workload.ref, steps)
        residuals = compute_residuals(model, ref_res.region_s,
                                      ref_res.replays)

    cands = enumerate_candidates(workload.kind,
                                 apus=workload.meta.get("apus", 4))
    if workload.ref not in cands:
        cands.append(workload.ref)
    scores = [score_candidate(c, model, residuals, kind=workload.kind,
                              meta=workload.meta) for c in cands]
    scored = sorted(zip(scores, range(len(cands)), cands))

    table = [{"candidate": c.to_dict(), "label": c.label, "score_s": s,
              "fom_s": None, "parity_max_err": None}
             for s, c in zip(scores, cands)]
    disqualified: List[str] = []

    # the winner pool: (fom, score, order, candidate) — ref always in it
    pool: List[tuple] = []
    if trials > 0:
        if ref_res is None:
            ref_res = measure(workload, workload.ref, steps)
        ref_i = cands.index(workload.ref)
        table[ref_i]["fom_s"] = ref_res.fom_s
        pool.append((ref_res.fom_s, scores[ref_i], ref_i, workload.ref))
        for i in _diverse_finalists(scored, trials):
            cand = cands[i]
            if cand == workload.ref:
                continue
            res = measure(workload, cand, steps)
            try:
                err = check_parity(res.leaves, ref_res.leaves)
            except AssertionError as exc:
                disqualified.append(f"{cand.label}: {exc}")
                table[i]["parity_max_err"] = "FAILED"
                continue
            table[i]["fom_s"] = res.fom_s
            table[i]["parity_max_err"] = err
            pool.append((res.fom_s, scores[i], i, cand))
        fom, score, _, winner = min(pool, key=lambda t: t[:3])
    else:
        score, _, winner = scored[0]
        fom = None

    ref_fom = ref_res.fom_s if ref_res is not None else None
    return TuneResult(workload=workload.name,
                      bucket=size_bucket(workload.size), winner=winner,
                      fom_s=fom, ref_fom_s=ref_fom, score_s=score,
                      residuals=dict(residuals), table=table,
                      disqualified=disqualified)


def load_variant_winners(
        path: str = "artifacts/variants/autotune_winners.json"
) -> Dict[str, str]:
    """The persisted AutotuneSelector cells (fig_variants artifact) the
    ``autotuned`` selector axis reuses; ``{}`` when never calibrated."""
    p = Path(path)
    if not p.exists():
        return {}
    try:
        return dict(json.loads(p.read_text()).get("winners", {}))
    except (ValueError, AttributeError):
        return {}


def tune_workloads(names, *, trials: int = 3, steps: Optional[int] = None,
                   out: Optional[str] = None,
                   winners_path: str = "artifacts/variants/autotune_winners.json",
                   profile: Optional[PolicyProfile] = None,
                   gate_tol: Optional[float] = None, seed: int = 0):
    """Tune each named workload and persist the winners.  Returns
    ``(profile, results)``.  ``gate_tol`` arms the tuned-vs-ref
    regression gate: any measured winner worse than its reference by
    more than the tolerance raises (the winner pool already contains
    the reference, so this only trips on measurement noise — the
    tolerance absorbs it)."""
    winners = load_variant_winners(winners_path)
    profile = profile or PolicyProfile()
    results = []
    failures = []
    for name in names:
        w = get_workload(name)
        res = tune(w, trials=trials, steps=steps, winners=winners, seed=seed)
        results.append(res)
        profile.add(res.to_entry(variant_winners=winners))
        if gate_tol is not None and res.fom_s is not None \
                and res.ref_fom_s is not None \
                and res.fom_s > res.ref_fom_s * (1.0 + gate_tol):
            failures.append(f"{name}: tuned {res.fom_s:.6f}s > ref "
                            f"{res.ref_fom_s:.6f}s * (1+{gate_tol})")
    if out:
        profile.save(out)
    if failures:
        raise SystemExit("[tune] regression gate failed:\n  "
                         + "\n  ".join(failures))
    return profile, results
