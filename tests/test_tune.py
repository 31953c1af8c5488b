"""Global policy autotuner (repro.tune): deterministic search, profile
persistence round-tripping into ``--policy auto``, nearest-bucket
fallback, and the cost-model-vs-measured rank-correlation smoke.

The search itself is pinned with an injected ``measure`` function — the
tuner's determinism contract is "same seed + same profile -> identical
winners", which only holds if nothing inside the search consults the
wall clock."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ledger import Ledger
from repro.core.program import capture
from repro.core.regions import (AdaptivePolicy, AutotuneSelector,
                                DiscretePolicy, StaticSelector,
                                UnifiedPolicy, region, size_bucket)
from repro.launch.mesh import near_square_mesh_shape
from repro.launch.policy import auto_policy
from repro.tune import tuner as TU
from repro.tune.profile import (PROFILE_VERSION, PolicyProfile, ProfileEntry,
                                entry_key)
from repro.tune.space import (PolicyCandidate, cfd_size, enumerate_candidates,
                              parse_winner_key, serve_size, train_size)
from repro.tune.workloads import RunResult, Workload, get_workload

N = 1 << 14


def _mini_program():
    """A two-region captured program the cost model can price."""
    ldg = Ledger("tune_prog")
    scale = region("TSCALE", ledger=ldg)(lambda d, x: d * x)
    saxpy = region("TSAXPY", ledger=ldg)(lambda a, x, y: y - a * x)

    def step(run, d, x, b):
        return run(saxpy, 1.0, run(scale, d, x), b)

    d = jnp.linspace(1.0, 2.0, N)
    x = jnp.full((N,), 0.3, jnp.float32)
    b = jnp.linspace(0.0, 1.0, N)
    return capture(step, d, x, b, name="tune_mini")


def _fake_workload(fom_by_placement, bad_leaves_for=()):
    """A workload whose 'measurements' are a deterministic lookup table:
    FOM per placement, reference leaves everywhere except the labels in
    ``bad_leaves_for`` (which fail the parity check)."""
    prog = _mini_program()

    def run(candidate, steps, winners=None):
        fom = fom_by_placement.get(candidate.label,
                                   fom_by_placement.get(candidate.placement,
                                                        1.0))
        leaves = [np.arange(8, dtype=np.float32)]
        if candidate.label in bad_leaves_for:
            leaves = [np.arange(8, dtype=np.float32) + 1.0]
        return RunResult(leaves=leaves, fom_s=fom,
                         region_s={"TSCALE": fom / 2, "TSAXPY": fom / 2},
                         replays=steps)

    return Workload(name="fake", kind="replay", size=1536, memory=None,
                    build_program=lambda: prog, run=run,
                    ref=PolicyCandidate(placement="discrete"), steps=2)


def _measure(w, c, s):
    return w.run(c, s)


# ---------------------------------------------------------------------------
# search space
# ---------------------------------------------------------------------------

def test_enumeration_deterministic_and_covers_placements():
    a = enumerate_candidates("replay")
    b = enumerate_candidates("replay")
    assert a == b                               # fixed order, fixed set
    assert {c.placement for c in a} == {"unified", "adaptive", "discrete",
                                        "host"}
    assert any(c.staging == "async" for c in a)          # discrete only
    assert all(c.placement == "discrete" for c in a if c.staging == "async")
    sh = enumerate_candidates("sharded", apus=4)
    assert {c.mesh for c in sh} == {(4,), (2, 2)}
    assert {c.schedule for c in sh} == {"sequential", "overlap", "split"}


def test_candidate_roundtrip_and_selector():
    c = PolicyCandidate(placement="adaptive", cutoff=4096,
                        selector="autotuned", mesh=(2, 2))
    assert PolicyCandidate.from_dict(c.to_dict()) == c
    sel = c.make_selector({"TSCALE|device|2^11": "pallas"})
    assert isinstance(sel, AutotuneSelector)
    assert sel.winners[("TSCALE", "device", 11)] == "pallas"
    assert isinstance(PolicyCandidate().make_selector(), StaticSelector)
    with pytest.raises(ValueError):
        parse_winner_key("no-bucket-suffix")


def test_build_policy_reconstructs_each_placement():
    assert isinstance(PolicyCandidate().build_policy(), UnifiedPolicy)
    assert isinstance(PolicyCandidate(placement="discrete").build_policy(),
                      DiscretePolicy)
    pol = PolicyCandidate(placement="adaptive", cutoff=4096).build_policy()
    assert isinstance(pol, AdaptivePolicy) and pol.cutoff == 4096


def test_near_square_mesh_shape():
    assert near_square_mesh_shape(1) == (1,)
    assert near_square_mesh_shape(4) == (2, 2)
    assert near_square_mesh_shape(6) == (2, 3)
    assert near_square_mesh_shape(8) == (2, 4)
    assert near_square_mesh_shape(12) == (3, 4)
    assert near_square_mesh_shape(7) == (7,)     # primes stay 1-D
    with pytest.raises(ValueError):
        near_square_mesh_shape(0)


# ---------------------------------------------------------------------------
# the search
# ---------------------------------------------------------------------------

def test_tuner_determinism_same_inputs_same_winner():
    w = _fake_workload({"unified": 0.1, "adaptive": 0.3, "discrete": 1.0,
                        "host": 2.0})
    r1 = TU.tune(w, trials=4, measure=_measure, seed=0)
    r2 = TU.tune(w, trials=4, measure=_measure, seed=0)
    assert r1.winner == r2.winner
    assert [t["label"] for t in r1.table] == [t["label"] for t in r2.table]
    assert [t["score_s"] for t in r1.table] == [t["score_s"] for t in r2.table]
    assert r1.winner.placement == "unified"      # the fastest fake FOM
    assert r1.fom_s == 0.1 and r1.ref_fom_s == 1.0


def test_winner_never_measured_worse_than_ref():
    # every searched candidate measures 10x slower than the reference;
    # the winner pool always contains the ref, so the ref must win
    w = _fake_workload({"unified": 10.0, "adaptive": 10.0, "host": 10.0,
                        "discrete": 10.0, "discrete+ref": 1.0})
    res = TU.tune(w, trials=3, measure=_measure)
    assert res.winner == w.ref and res.fom_s == 1.0


def test_parity_failure_disqualifies_candidate():
    w = _fake_workload({"unified": 0.01, "discrete": 1.0},
                       bad_leaves_for=("unified+ref",))
    res = TU.tune(w, trials=1, measure=_measure)
    assert any("unified+ref" in d for d in res.disqualified)
    assert res.winner != PolicyCandidate()       # the cheater did not win


def test_trials_zero_is_pure_cost_model():
    w = _fake_workload({})
    calls = []
    res = TU.tune(w, trials=0, residuals={"*": 1.0},
                  measure=lambda *a: calls.append(a))
    assert not calls                             # measurement-free
    assert res.fom_s is None and res.ref_fom_s is None
    # the UPM-seeded priors rank unified ahead of staged/host placements
    assert res.winner.placement == "unified"
    res2 = TU.tune(w, trials=0, residuals={"*": 1.0},
                   measure=lambda *a: calls.append(a))
    assert res.winner == res2.winner and res.score_s == res2.score_s


def test_residuals_correct_the_model():
    prog = _mini_program()
    model = TU.model_costs(prog)
    assert model["total_s"] > 0 and model["ops"]
    meas = {r: 10.0 * t for r, t in model["region_s"].items()}
    res = TU.compute_residuals(model, meas)
    assert res["*"] == pytest.approx(10.0)
    for r in model["region_s"]:
        assert res[r] == pytest.approx(10.0)
    base = TU.score_candidate(PolicyCandidate(), model)
    corrected = TU.score_candidate(PolicyCandidate(), model, res)
    assert corrected == pytest.approx(10.0 * base)


def test_scores_rank_placements_by_prior():
    model = TU.model_costs(_mini_program())
    s = {p: TU.score_candidate(PolicyCandidate(placement=p), model)
         for p in ("unified", "discrete", "host")}
    assert s["unified"] < s["discrete"]          # staging tax
    assert s["unified"] < s["host"]              # host-compute factor


# ---------------------------------------------------------------------------
# profile persistence + --policy auto
# ---------------------------------------------------------------------------

def test_profile_roundtrip_constructs_exact_winning_policy(tmp_path):
    w = _fake_workload({"adaptive": 0.1, "unified": 0.5, "discrete": 1.0})
    res = TU.tune(w, trials=4, measure=_measure)
    assert res.winner.placement == "adaptive"
    path = tmp_path / "profile.json"
    prof = PolicyProfile()
    prof.add(res.to_entry())
    prof.save(path)

    loaded = PolicyProfile.load(path)
    entry = loaded.lookup("fake", w.size)
    assert entry is not None and entry.candidate == res.winner
    assert entry.fom_s == res.fom_s and entry.ref_fom_s == res.ref_fom_s

    pol = auto_policy("fake", w.size, profile_path=str(path), quiet=True)
    assert isinstance(pol, AdaptivePolicy)
    assert pol.cutoff == (res.winner.cutoff or pol.cutoff)
    assert pol.tuned_entry.key == entry_key("fake", size_bucket(w.size))


def test_profile_version_gate(tmp_path):
    path = tmp_path / "profile.json"
    prof = PolicyProfile()
    prof.add(ProfileEntry("fake", 11, PolicyCandidate()))
    prof.save(path)
    d = path.read_text().replace(f'"version": {PROFILE_VERSION}',
                                 '"version": 999')
    path.write_text(d)
    with pytest.raises(ValueError):
        PolicyProfile.load(path)
    # but a MISSING profile is "no profile", not an error
    assert PolicyProfile.load_if_exists(tmp_path / "nope.json") is None


def test_nearest_bucket_fallback(tmp_path):
    prof = PolicyProfile()
    e8 = ProfileEntry("fake", 8, PolicyCandidate(placement="host"))
    e12 = ProfileEntry("fake", 12, PolicyCandidate(placement="discrete"))
    prof.add(e8)
    prof.add(e12)
    assert prof.lookup("fake", 2 ** 11).bucket == 12       # exact bucket
    assert prof.lookup("fake", 2 ** 20).bucket == 12       # nearest above
    assert prof.lookup("fake", 4).bucket == 8              # nearest below
    # distance tie resolves to the smaller bucket (AutotuneSelector rule)
    assert prof.lookup("fake", 2 ** 9 + 1).bucket == 8
    assert prof.lookup("unknown", 2 ** 11) is None

    path = tmp_path / "profile.json"
    prof.save(path)
    # an unknown workload falls back to the hand-assembled lm_policy
    pol = auto_policy("unknown", 1024, profile_path=str(path), quiet=True)
    assert isinstance(pol, UnifiedPolicy) and pol.tuned_entry is None


def test_size_helpers_match_bucket_scheme():
    assert serve_size(2, 12, 64) == 1536
    assert train_size(2, 16, 64) == 2048
    assert cfd_size((12, 12, 12)) == 1728
    assert size_bucket(serve_size(2, 12, 64)) == 11


# ---------------------------------------------------------------------------
# cost model vs measured (the calibration smoke)
# ---------------------------------------------------------------------------

def test_cost_model_rank_correlation_on_cfd_corpus():
    """The roofline bridge must get the per-region *ranking* right on a
    real shipped program — that is all the pruning stage needs from it
    (the measured finalist pass owns absolute ordering)."""
    w = get_workload("cfd_step")
    model = TU.model_costs(w.build_program())
    assert not model["skipped"], model["skipped"]
    res = w.run(PolicyCandidate(), 2, calibrate=True)
    common = [r for r in res.region_s if r in model["region_s"]]
    assert len(common) >= 8, common
    m = np.array([model["region_s"][r] for r in common])
    s = np.array([res.region_s[r] for r in common])
    rank = lambda v: np.argsort(np.argsort(v))
    corr = float(np.corrcoef(rank(m), rank(s))[0, 1])
    assert corr > 0.5, (corr, dict(zip(common, zip(m, s))))


@pytest.mark.parametrize("placement", ["unified", "discrete"])
def test_region_seconds_come_from_synced_calls(monkeypatch, placement):
    """The hot path's Executor waits only for a staged call; the
    calibration replays the candidate's policy through a SyncedExecutor,
    which waits for every call, and a staged call's copies stay out of
    the region seconds."""
    import time

    from repro.tune import workloads as WL
    slow = 0.2                                # seconds each copy-out takes
    prog = _mini_program()
    d, x, b = prog._example_in_leaves
    ex = WL._executor(PolicyCandidate(placement=placement), None, None,
                      "tune_synced")
    prog.replay(ex, d, x, b)                  # compile outside the count
    staged = ex.policy.stager.stages
    assert ex.ledger.syncs == (2 if staged else 0)
    if staged:
        stage_out = ex.policy.stager.stage_out

        def slow_stage_out(*args):
            out, s, nbytes = stage_out(*args)
            time.sleep(slow)
            return out, s + slow, nbytes

        monkeypatch.setattr(ex.policy.stager, "stage_out", slow_stage_out)
    replays = []
    region_s = WL._region_seconds(
        ex, lambda e: replays.append((e, prog.replay(e, d, x, b))), True)
    ((synced, out),) = replays
    np.testing.assert_allclose(np.asarray(out), np.asarray(b - 0.3 * d),
                               rtol=1e-6)
    rows = synced.ledger.regions
    assert synced.ledger.syncs == sum(r.calls for r in rows.values()) == 2
    assert sorted(region_s) == ["TSAXPY", "TSCALE"]
    assert all(t > 0 for t in region_s.values())
    staging = sum(r.staging_s for r in rows.values())
    assert staging >= (2 * slow if staged else 0.0)
    assert sum(region_s.values()) < slow, (region_s, staging)
    assert WL._region_seconds(ex, lambda e: replays.append(e), False) == {}
    assert len(replays) == 1                  # no calibration, no replay


def test_tune_calibrates_on_the_reference_run_only():
    """Only the reference run measures region seconds (on the synced
    path); every finalist's FOM is taken the way the hot path runs."""
    w = _fake_workload({"discrete": 2.0, "unified": 1.0})
    fake_run, seen = w.run, []

    def run(candidate, steps, winners=None, calibrate=False):
        seen.append((candidate, calibrate))
        return fake_run(candidate, steps, winners)

    w.run = run
    TU.tune(w, trials=3)
    assert seen[0] == (w.ref, True)
    assert len(seen) > 1
    assert all(c != w.ref and not cal for c, cal in seen[1:])
