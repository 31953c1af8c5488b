"""The ML stack on the region-program spine (serve + train).

Covers: role-keyed KV placement (``offload_kv_cache`` as a Placer), decode
bit-parity with and without KV offload, ``replay_batch`` decode parity vs
N sequential replays, the region-decomposed train step (``FWD_BWD`` /
``ADAMW_UPDATE``) vs the raw jit step, the AdamW ``host`` variant,
supervisor restarts that re-capture while keeping the same Ledger, and the
coverage_report() snapshot saved beside checkpoint weights."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.reduced import reduced as make_reduced
from repro.configs.registry import get_config
from repro.core.ledger import Ledger
from repro.core.program import capture
from repro.core.regions import (Executor, HostPolicy, Placer, TargetSelector,
                                UnifiedPolicy, region)
from repro.core.umem import preferred_host_space
from repro.launch import serve as SV
from repro.launch.mesh import make_smoke_mesh
from repro.launch.policy import lm_policy
from repro.models import transformer as T
from repro.optim import adamw
from repro.train import step as S


# ---------------------------------------------------------------------------
# role-keyed KV placement
# ---------------------------------------------------------------------------

def _recording_tree_place(monkeypatch):
    """Swap serve's tree_place for a recorder: the test asserts which
    leaves are placed, not where they land."""
    calls = []

    def rec(tree, space, device=None, min_bytes=0):
        calls.append((tuple(np.asarray(x).shape
                            for x in jax.tree.leaves(tree)), min_bytes))
        return tree

    monkeypatch.setattr(SV, "tree_place", rec)
    return calls


def test_place_kv_leaves_moves_only_kv_roles(monkeypatch):
    calls = _recording_tree_place(monkeypatch)
    cache = {"cycles": {"p0": {"k": jnp.ones((2, 8, 1, 16)),
                               "v": jnp.ones((2, 8, 1, 16)),
                               "pos": jnp.ones((8,), jnp.int32)}},
             "x_cm": jnp.ones((2, 64))}
    host = preferred_host_space()
    if host is None:
        pytest.skip("no host memory space on this platform")
    out = SV.place_kv_leaves(cache, host, min_bytes=123)
    # only the two k/v leaves were offered to tree_place, with min_bytes
    # threaded through (the size gate itself is tree_place's, covered in
    # test_regions); pos and x_cm never cross
    assert len(calls) == 2
    assert all(mb == 123 for _, mb in calls)
    assert all(shapes == ((2, 8, 1, 16),) for shapes, _ in calls)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_offload_kv_cache_is_a_placer(monkeypatch):
    host = preferred_host_space()
    if host is None:
        pytest.skip("no host memory space on this platform")
    placer = SV.offload_kv_cache(min_bytes=7)
    assert isinstance(placer, Placer)          # a policy placement axis
    assert placer.kv_space == host and placer.kv_min_bytes == 7
    calls = _recording_tree_place(monkeypatch)

    @region("kv-dummy", ledger=Ledger("t"))
    def f(tok, cache):
        return cache

    cache = {"k": jnp.ones((4, 16)), "v": jnp.ones((4, 16)),
             "pos": jnp.ones((16,), jnp.int32)}
    args, kwargs = placer.place_args(f, (jnp.ones(2), cache), {})
    assert len(calls) == 2                     # k and v of the args tree
    out = placer.place_result(f, cache)
    assert len(calls) == 4                     # + k and v of the result
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_place_preserves_named_sharding():
    """Placing a mesh-sharded array into host space must rebind the memory
    kind, not gather onto one device — FSDP moments / scattered KV caches
    keep their partitioning under the placement axis."""
    from repro.core.umem import place
    host = preferred_host_space()
    if host is None:
        pytest.skip("no host memory space on this platform")
    mesh = make_smoke_mesh()
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    x = jax.device_put(jnp.ones(128), sh)
    y = place(x, host)
    assert isinstance(y.sharding, jax.sharding.NamedSharding)
    assert y.sharding.memory_kind == host.kind
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


# ---------------------------------------------------------------------------
# serve programs (model-backed; one shared reduced setup)
# ---------------------------------------------------------------------------

BATCH, PROMPT, GEN = 2, 8, 4


@pytest.fixture(scope="module")
def serve_setup():
    cfg = make_reduced(get_config("tinyllama-1.1b"))
    mesh = make_smoke_mesh()
    key = jax.random.PRNGKey(0)
    params = T.init(key, cfg)
    prompts = jax.random.randint(key, (BATCH, PROMPT), 0, cfg.vocab,
                                 jnp.int32)
    batch_in = {"tokens": prompts}
    regions = SV.make_serve_regions(cfg, mesh, params,
                                    ledger=Ledger("serve_tests"))
    make_cache = lambda: T.init_cache(cfg, BATCH, PROMPT + GEN)
    prefill_prog = SV.capture_prefill_program(regions, batch_in,
                                              make_cache())
    ex = Executor(UnifiedPolicy(), Ledger("setup"))
    tok, cache = prefill_prog.replay(ex, batch_in, make_cache())
    decode_prog = SV.capture_decode_program(regions, PROMPT, GEN, tok, cache)
    return {"cfg": cfg, "params": params, "batch_in": batch_in,
            "regions": regions, "make_cache": make_cache,
            "prefill_prog": prefill_prog, "decode_prog": decode_prog}


def _decode_tokens(s, ex):
    tok, cache = s["prefill_prog"].replay(ex, s["batch_in"],
                                          s["make_cache"]())
    toks = s["decode_prog"].replay(ex, tok, cache)
    return np.asarray(jnp.stack(toks, axis=1))


def test_decode_bit_identical_with_and_without_kv_offload(serve_setup):
    s = serve_setup
    host = preferred_host_space()
    if host is None:
        pytest.skip("no host memory space on this platform")
    plain = Executor(UnifiedPolicy(), Ledger("plain"))
    # min_bytes=0 forces even smoke-scale k/v pages across the boundary
    offl = Executor(lm_policy("unified", s["cfg"].memory,
                              placer=SV.offload_kv_cache(min_bytes=0)),
                    Ledger("offl"))
    seq_plain = _decode_tokens(s, plain)
    seq_offl = _decode_tokens(s, offl)
    assert seq_plain.shape == (BATCH, GEN)
    np.testing.assert_array_equal(seq_plain, seq_offl)


def test_replay_batch_decode_parity_vs_sequential(serve_setup):
    s = serve_setup
    ex = Executor(UnifiedPolicy(), Ledger("batch"))
    toks, caches = [], []
    for r in range(2):
        key = jax.random.fold_in(jax.random.PRNGKey(7), r)
        prompts = jax.random.randint(key, (BATCH, PROMPT), 0,
                                     s["cfg"].vocab, jnp.int32)
        tok, cache = s["prefill_prog"].replay(ex, {"tokens": prompts},
                                              s["make_cache"]())
        toks.append(tok)
        caches.append(cache)
    stacked_tok = jnp.stack(toks)
    stacked_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
    out = s["decode_prog"].replay_batch(stacked_tok, stacked_cache,
                                        executor=ex)
    batched = np.asarray(jnp.stack(out, axis=-1))          # (N, B, gen)
    solo = np.stack([
        np.asarray(jnp.stack(s["decode_prog"].replay(ex, toks[i], caches[i]),
                             axis=-1))
        for i in range(2)])
    np.testing.assert_array_equal(batched, solo)
    # accounted as one ledger row on the executor's ledger
    assert any(name.startswith("decode_program[batch]")
               for name in ex.ledger.regions)


def test_serve_regions_account_on_one_ledger(serve_setup):
    s = serve_setup
    ex = Executor(UnifiedPolicy(), Ledger("acct"))
    _decode_tokens(s, ex)
    rep = ex.report()
    rows = set(ex.ledger.regions)
    assert {"PREFILL", "DECODE_STEP", "KV_APPEND"} <= rows
    assert rep["impl_counts"].get("ref", 0) >= 1 + 2 * (GEN - 1)
    assert 0 < rep["device_fraction"] <= 1    # KV_APPEND commits host-side


# ---------------------------------------------------------------------------
# train regions
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def train_setup():
    cfg = make_reduced(get_config("tinyllama-1.1b"))
    opt_cfg = adamw.AdamWConfig(lr=1e-3)
    key = jax.random.PRNGKey(1)
    params = T.init(key, cfg)
    opt = adamw.init_state(params, opt_cfg)
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab,
                                          jnp.int32)}
    return {"cfg": cfg, "opt_cfg": opt_cfg, "state": (params, opt),
            "batch": batch}


def test_train_regions_match_raw_step(train_setup):
    t = train_setup
    ldg = Ledger("train_regions")
    regions = S.make_train_regions(t["cfg"], t["opt_cfg"], ledger=ldg)
    prog = S.capture_train_program(regions, t["state"], t["batch"])
    ex = Executor(UnifiedPolicy(), ldg)
    (params_r, opt_r), metrics_r = prog.replay(ex, t["state"], t["batch"])

    raw = jax.jit(S.make_train_step(t["cfg"], t["opt_cfg"]))
    params_j, opt_j, metrics_j = raw(t["state"][0], t["state"][1],
                                     t["batch"])
    np.testing.assert_allclose(float(metrics_r["loss"]),
                               float(metrics_j["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(params_r), jax.tree.leaves(params_j)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-5)
    rows = set(ldg.regions)
    assert {"FWD_BWD", "ADAMW_UPDATE"} <= rows
    assert ex.report()["impl_counts"] == {"ref": 2}


def test_adamw_host_variant_bitwise_parity():
    key = jax.random.PRNGKey(3)
    cfg = adamw.AdamWConfig(lr=1e-2)
    params = {"a": jax.random.normal(key, (17, 5)),
              "b": {"w": jax.random.normal(jax.random.fold_in(key, 1),
                                           (8,))}}
    grads = jax.tree.map(lambda p: p * 0.3 + 0.01, params)
    state = adamw.init_state(params, cfg)
    ref = adamw.apply_updates(params, grads, state, cfg)
    host = adamw.apply_updates_leafwise(params, grads, state, cfg)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(host)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_host_policy_selects_adamw_host_variant(train_setup):
    t = train_setup
    ldg = Ledger("host_variant")
    regions = S.make_train_regions(t["cfg"], t["opt_cfg"], ledger=ldg)
    assert "host" in regions.adamw_update.variants
    ex = Executor(HostPolicy(selector=TargetSelector()), ldg)
    prog = S.capture_train_program(regions, t["state"], t["batch"])
    prog.replay(ex, t["state"], t["batch"])
    counts = ex.report()["impl_counts"]
    # FWD_BWD has no host variant -> declare-variant fallback to ref;
    # ADAMW_UPDATE runs its registered host implementation
    assert counts == {"ref": 1, "host": 1}
    assert ldg.regions["ADAMW_UPDATE"].impl == "host"


def test_optimizer_offload_is_a_placement_hint(train_setup):
    t = train_setup
    host = preferred_host_space()
    if host is None:
        pytest.skip("no host memory space on this platform")
    regions = S.make_train_regions(t["cfg"], t["opt_cfg"],
                                   ledger=Ledger("hint"),
                                   offload_optimizer=True)
    assert regions.adamw_update.arg_spaces == {"opt_state": host}
    # keyed result hint: only opt_state (element 1) re-homes host-side, so
    # moments stay host-resident BETWEEN steps without dragging params along
    assert regions.adamw_update.result_space == {1: host}
    out = Placer().place_result(
        regions.adamw_update,
        (jnp.ones(3), {"m": jnp.ones(4)}, jnp.float32(0.5)))
    assert isinstance(out, tuple) and len(out) == 3
    np.testing.assert_array_equal(np.asarray(out[1]["m"]), 1.0)
    plain = S.make_train_regions(t["cfg"], t["opt_cfg"],
                                 ledger=Ledger("nohint"))
    assert plain.adamw_update.arg_spaces is None
    assert plain.adamw_update.result_space is None


# ---------------------------------------------------------------------------
# supervisor re-capture + checkpoint coverage snapshot
# ---------------------------------------------------------------------------

def test_supervisor_recapture_keeps_ledger_rows(tmp_path):
    from repro.checkpoint.ckpt import Checkpointer
    from repro.runtime.fault import FaultInjector, TrainSupervisor

    ldg = Ledger("sup")

    @region("STEP", ledger=ldg)
    def step_region(x):
        return x * 0.9

    ex = Executor(UnifiedPolicy(), ldg)
    captures = []

    def make_step(state):
        prog = capture(lambda run, s: run(step_region, s), state)
        captures.append(prog)
        return lambda s, batch: (prog.replay(ex, s),
                                 {"loss": jnp.sum(jnp.abs(s))})

    state0 = jnp.ones(32)
    ckpt = Checkpointer(str(tmp_path), keep=3, async_save=False)
    sup = TrainSupervisor(make_step(state0), lambda step: None, ckpt,
                          ckpt_every=2, fault=FaultInjector({3}),
                          rebuild_step=lambda st, step: make_step(st),
                          report_fn=ex.report)
    state, rep = sup.run(state0, 0, 6)
    assert rep.restarts == 1
    assert len(captures) == 2                 # initial + post-restore
    # the re-capture reused the SAME region: one ledger row, no STEP#2
    assert set(ldg.regions) == {"STEP"}
    assert ldg.regions["STEP"].calls >= 6
    # every committed checkpoint carries the coverage snapshot
    steps = ckpt.all_steps()
    assert steps
    for s in steps:
        cov = tmp_path / f"step_{s:010d}" / "coverage.json"
        assert cov.exists()
    snap = json.loads(cov.read_text())
    assert snap["regions"] == 1 and snap["mode"] == "unified"
    np.testing.assert_allclose(np.asarray(state),
                               np.asarray(state0) * 0.9 ** 6, rtol=1e-6)


def test_checkpoint_save_without_report_has_no_coverage_file(tmp_path):
    from repro.checkpoint.ckpt import Checkpointer
    ck = Checkpointer(str(tmp_path), async_save=False)
    ck.save(1, {"w": jnp.ones(4)}, extra={"step": 1})
    d = tmp_path / "step_0000000001"
    assert (d / "manifest.json").exists()
    assert not (d / "coverage.json").exists()


# ---------------------------------------------------------------------------
# driver acceptance: --policy/--report emit the canonical report
# ---------------------------------------------------------------------------

def _json_tail(out: str) -> dict:
    return json.loads(out[out.index("\n{") + 1:])


def test_serve_main_report_emits_coverage(capsys):
    from repro.launch.serve import main
    seq = main(["--arch", "tinyllama-1.1b", "--reduced", "--batch", "2",
                "--prompt-len", "8", "--gen", "4", "--report"])
    assert seq.shape == (2, 4)
    rep = _json_tail(capsys.readouterr().out)
    assert rep["mode"] == "unified"
    assert sum(rep["impl_counts"].values()) > 0
    assert 0 < rep["device_fraction"] <= 1


def test_train_main_report_emits_coverage(capsys):
    from repro.launch.train import main
    losses = main(["--arch", "tinyllama-1.1b", "--reduced", "--steps", "2",
                   "--batch", "2", "--seq", "16", "--report"])
    assert np.isfinite(losses).all()
    rep = _json_tail(capsys.readouterr().out)
    assert rep["mode"] == "unified"
    assert rep["impl_counts"].get("ref", 0) == 4      # 2 regions x 2 steps
    assert rep["device_fraction"] > 0


# ---------------------------------------------------------------------------
# KVCachePlacer edge cases: the min_bytes boundary, role misses, idempotence
# ---------------------------------------------------------------------------

def _recording_place(monkeypatch):
    """Record which leaves tree_place actually offers to umem.place — the
    size gate lives inside tree_place, so this sees its decisions."""
    import repro.core.umem as U
    offered = []

    def rec(x, space, device=None):
        offered.append(x)
        return x

    monkeypatch.setattr(U, "place", rec)
    return offered


def test_kv_placer_leaf_exactly_at_min_bytes_moves(monkeypatch):
    """The threshold is `nbytes < min_bytes stays`: a leaf exactly AT the
    boundary crosses (the paper's 'pool above 5K elements' cut applied to
    placement is inclusive on the budget side)."""
    host = preferred_host_space()
    if host is None:
        pytest.skip("no host memory space on this platform")
    offered = _recording_place(monkeypatch)
    at = jnp.ones((8,), jnp.float32)            # 32 bytes == min_bytes
    below = jnp.ones((7,), jnp.float32)         # 28 bytes  < min_bytes
    cache = {"k": at, "v": below, "pos": jnp.ones((64,), jnp.int32)}
    out = SV.place_kv_leaves(cache, host, min_bytes=32)
    assert len(offered) == 1 and offered[0] is at
    assert out["v"] is below                    # skipped leaf: same object
    assert out["pos"] is cache["pos"]           # non-kv role: never offered


def test_kv_placer_no_kv_leaves_is_identity(monkeypatch):
    """A tree with no k/v-keyed leaves comes back leaf-identical — the
    role keying never touches (or copies) bystander state."""
    host = preferred_host_space()
    if host is None:
        pytest.skip("no host memory space on this platform")
    offered = _recording_place(monkeypatch)
    tree = {"x_cm": jnp.ones((4, 64)), "pos": jnp.ones((16,), jnp.int32),
            "nested": {"state": jnp.zeros((2, 8))}}
    out = SV.place_kv_leaves(tree, host, min_bytes=0)
    assert not offered
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        assert a is b


def test_kv_placer_idempotent_when_already_in_host_space():
    """Placing twice is placing once: the second pass is a memory-kind
    no-op and values never change (place never rewrites data)."""
    from repro.core.umem import space_of
    host = preferred_host_space()
    if host is None:
        pytest.skip("no host memory space on this platform")
    cache = {"k": jnp.arange(64, dtype=jnp.float32).reshape(4, 16),
             "v": jnp.ones((4, 16)), "pos": jnp.ones((16,), jnp.int32)}
    once = SV.place_kv_leaves(cache, host, min_bytes=0)
    twice = SV.place_kv_leaves(once, host, min_bytes=0)
    assert space_of(twice["k"]) == host.kind
    assert space_of(twice["v"]) == host.kind
    for a, b in zip(jax.tree.leaves(twice), jax.tree.leaves(cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
