"""The program's host spans and host round-trip counters.

Spans are read back from a real profiler trace on the CPU: a region call
opens ``repro:region:<name>`` around ``repro:dispatch``, and around
``repro:sync`` only when the call is staged or a SyncedExecutor runs
it; a sharded replay tags every span of an op with its index, on the
overlap worker too.  The counters:
``readbacks`` per scalar the solver reads (only where a tolerance
controls its loop), ``syncs`` per wait that happens, reset and merged
like the other per-run numbers.  A call the Executor does not wait for
gives the same bits as one it waits for.
"""
import functools
import glob
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cfd import fvm
from repro.cfd.grid import Grid
from repro.cfd.precond import rb_dilu_factor
from repro.cfd.simple import SimpleConfig, SimpleFoam, SimpleState
from repro.cfd.solvers import make_solver_regions, pbicgstab_regions
from repro.core.ledger import Ledger, readback, span
from repro.core.program import capture
from repro.core.regions import (DiscretePolicy, Executor, HostPolicy,
                                SyncedExecutor, UnifiedPolicy, region)
from repro.core.shard_program import shard_program
from repro.launch.mesh import make_apu_mesh

SRC = Path(__file__).resolve().parent.parent / "src"


def traced(fn, tmp_path):
    """Run ``fn`` under the profiler; its result and the host's
    ``repro:`` events as (line index, name, start, end, stats)."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro:"):
                    events.append((i, ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns,
                                   dict(ev.stats)))
    return out, events


def inside(inner, outer) -> bool:
    return inner[0] == outer[0] and outer[2] <= inner[2] \
        and inner[3] <= outer[3]


def test_span_names_and_stats():
    assert span("dispatch") is not None
    with span("region", "dot#2", op=3, impl="ref"):
        pass                        # inert without a profiler session


def test_executor_run_opens_region_dispatch_sync(tmp_path):
    """An unstaged call is dispatched and not waited for: no ``sync``."""
    ledger = Ledger("spans")

    @region("axpy", ledger=ledger)
    def axpy(a, x, y):
        return a * x + y

    ex = Executor(UnifiedPolicy(), ledger)
    x = jnp.ones(64)
    ex.run(axpy, 2.0, x, x)                   # compile outside the trace
    out, events = traced(lambda: ex.run(axpy, 2.0, x, x), tmp_path)
    np.testing.assert_allclose(np.asarray(out), 3.0)
    (reg,) = [e for e in events if e[1] == "repro:region:axpy"]
    assert reg[4] == {"impl": "ref", "target": "default"}
    (disp,) = [e for e in events if e[1] == "repro:dispatch"]
    assert inside(disp, reg)
    assert not [e for e in events if e[1] == "repro:sync"]
    assert ledger.syncs == 0 and ledger.regions["axpy"].calls == 2


@pytest.mark.parametrize("executor, policy, target", [
    (Executor, DiscretePolicy, "device"),
    (SyncedExecutor, UnifiedPolicy, "default")])
def test_waited_call_syncs_inside_its_region(tmp_path, executor, policy,
                                             target):
    """The stager reads a staged call's result on the host, so the call
    waits for it, as a SyncedExecutor waits for every call: ``sync``
    after ``dispatch``, both inside the region."""
    ledger = Ledger("waited_spans")

    @region("axpy", ledger=ledger)
    def axpy(a, x, y):
        return a * x + y

    ex = executor(policy(), ledger)
    x = jnp.ones(64)
    ex.run(axpy, 2.0, x, x)                   # compile outside the trace
    out, events = traced(lambda: ex.run(axpy, 2.0, x, x), tmp_path)
    np.testing.assert_allclose(np.asarray(out), 3.0)
    (reg,) = [e for e in events if e[1] == "repro:region:axpy"]
    assert reg[4] == {"impl": "ref", "target": target}
    (disp,) = [e for e in events if e[1] == "repro:dispatch"]
    (sync,) = [e for e in events if e[1] == "repro:sync"]
    assert inside(disp, reg) and inside(sync, reg)
    assert disp[3] <= sync[2]                 # dispatch, then the wait
    assert ledger.syncs == 2 and ledger.regions["axpy"].calls == 2


@pytest.mark.parametrize("iters,tol", [
    pytest.param(1, 0.0, id="1"), pytest.param(3, 0.0, id="3"),
    pytest.param(1, 1e-12, id="1-tol"), pytest.param(3, 1e-12, id="3-tol")])
def test_pbicgstab_counts_readbacks_and_syncs(iters, tol):
    g = Grid((8, 8, 8))
    A, _ = fvm.laplacian(g, 1.0)
    b = jnp.asarray(np.random.RandomState(0).rand(*g.shape)
                    .astype(np.float32))
    red, _ = g.red_black_masks()
    P = rb_dilu_factor(A, red)
    ledger = Ledger("solve")
    ex = Executor(UnifiedPolicy(), ledger)
    res = pbicgstab_regions(ex, make_solver_regions(ledger), A, b,
                            jnp.zeros_like(b), P, tol=tol, max_iter=iters)
    assert res.iters == iters
    # no tolerance: nothing read; else the entry residual, then one value
    # an iteration
    assert ledger.readbacks == (1 + iters if tol > 0 else 0)
    calls = sum(r.calls for r in ledger.regions.values())
    assert calls == 4 + 13 * iters
    assert ledger.syncs == 0          # only a readback waits for the device


def solve_8(executor, tol=0.0):
    g = Grid((8, 8, 8))
    A, _ = fvm.laplacian(g, 1.0)
    b = jnp.asarray(np.random.RandomState(1).rand(*g.shape)
                    .astype(np.float32))
    red, _ = g.red_black_masks()
    P = rb_dilu_factor(A, red)
    res = pbicgstab_regions(executor, make_solver_regions(executor.ledger),
                            A, b, jnp.zeros_like(b), P, tol=tol, max_iter=3)
    assert res.iters == 3
    return [res.x], [res.initial_residual, res.final_residual]


def time_step_8(executor):
    cfg = SimpleConfig(grid=Grid((8, 8, 8)), inner_max=6)
    rng = np.random.RandomState(2)
    st = SimpleState(*(jnp.asarray(1e-3 * rng.randn(8, 8, 8)
                                   .astype(np.float32)) for _ in range(4)))
    new, m = SimpleFoam(cfg).time_step(st, executor=executor)
    return [new.u, new.v, new.w, new.p], [m["res_u"], m["res_p"]]


@pytest.mark.parametrize("work,reads", [
    pytest.param(solve_8, 0, id="solve_8"),
    pytest.param(time_step_8, None, id="time_step_8"),
    pytest.param(functools.partial(solve_8, tol=1e-12), 1 + 3,
                 id="solve_8-tol")])
def test_unsynced_calls_give_the_same_bits(work, reads):
    """``reads`` None: a tolerance controls every solve, so some."""
    unsynced = Executor(UnifiedPolicy(), Ledger("unsynced"))
    synced = SyncedExecutor(UnifiedPolicy(), Ledger("synced"))
    fields, scalars = work(unsynced)
    fields_s, scalars_s = work(synced)
    for a, b in zip(fields, fields_s):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert list(map(float, scalars)) == list(map(float, scalars_s))
    assert unsynced.ledger.syncs == 0
    assert synced.ledger.syncs == sum(
        r.calls for r in synced.ledger.regions.values()) > 0
    assert unsynced.ledger.readbacks == synced.ledger.readbacks
    if reads is None:
        assert unsynced.ledger.readbacks > 0
    else:
        assert unsynced.ledger.readbacks == reads


def traced_solve_4(tmp_path, tol):
    """One iteration of a 4^3 solve, traced after a warm-up; its result,
    its ``repro:solve`` event and its ``repro:readback`` events."""
    g = Grid((4, 4, 4))
    A, _ = fvm.laplacian(g, 1.0)
    b = jnp.ones(g.shape, jnp.float32)
    red, _ = g.red_black_masks()
    P = rb_dilu_factor(A, red)
    ex = Executor(UnifiedPolicy(), Ledger("solve"))
    regions = make_solver_regions(ex.ledger)

    def solve():
        return pbicgstab_regions(ex, regions, A, b, jnp.zeros_like(b), P,
                                 tol=tol, max_iter=1)

    solve()
    res, events = traced(solve, tmp_path)
    assert res.iters == 1
    (sol,) = [e for e in events if e[1] == "repro:solve"]
    reads = [e for e in events if e[1] == "repro:readback"]
    return events, sol, reads


def test_pbicgstab_reads_back_inside_the_solve(tmp_path):
    """Under a tolerance: the entry residual, then one value an
    iteration, each read inside the solve and outside every region."""
    events, sol, reads = traced_solve_4(tmp_path, 1e-12)
    assert len(reads) == 1 + 1 and all(inside(e, sol) for e in reads)
    assert not any(inside(r, e) for r in reads for e in events
                   if e[1].startswith("repro:region:"))


def test_pbicgstab_reads_nothing_back_without_a_tolerance(tmp_path):
    _, _, reads = traced_solve_4(tmp_path, 0.0)
    assert reads == []


def test_readback_counts_on_the_ledger():
    ledger = Ledger("rb")
    assert readback(jnp.float32(2.5), ledger) == 2.5
    assert readback(jnp.float32(1.0)) == 1.0    # no ledger: not counted
    assert ledger.readbacks == 1


def test_counters_reset_merge_and_report():
    a, b = Ledger("a"), Ledger("b")
    a.readbacks, a.syncs = 3, 5
    b.readbacks, b.syncs = 4, 6
    node = Ledger.merged([a, b])
    assert (node.readbacks, node.syncs) == (7, 11)
    rep = node.coverage_report()
    assert (rep["readbacks"], rep["syncs"]) == (7, 11)
    a.merge_from(b)
    assert (a.readbacks, a.syncs) == (7, 11)
    a.reset_timings()
    assert (a.readbacks, a.syncs) == (0, 0)
    b.clear()
    assert (b.readbacks, b.syncs) == (0, 0)


def stencil_program(ledger):
    """Two chained stencil calls and a pointwise one over an 8x8x8 field."""
    from repro.cfd.dia import STENCIL_OFFSETS

    @region("stencil", stencil=STENCIL_OFFSETS, halo_args=("x",),
            ledger=ledger)
    def stencil(x):
        nz = x.shape[2]
        return x + jnp.pad(x, ((0, 0), (0, 0), (1, 0)))[:, :, :nz]

    @region("scale", ledger=ledger)
    def scale(x):
        return 0.5 * x

    def step(run, x):
        return run(scale, run(stencil, run(stencil, x)))

    x = jnp.linspace(0.0, 1.0, 512, dtype=jnp.float32).reshape(8, 8, 8)
    return capture(step, x, name="stencils"), x


def test_sharded_replay_tags_every_span_with_its_op(tmp_path):
    prog, x = stencil_program(Ledger("shard_spans"))
    sp = shard_program(prog, make_apu_mesh(1), UnifiedPolicy(),
                       overlap=True)
    sp.replay(x)                              # compile outside the trace
    sp.executor.reset_timings()
    _, events = traced(lambda: sp.replay(x), tmp_path)
    regions = [e for e in events if e[1].startswith("repro:region:")]
    assert [e[4]["op"] for e in regions] == list(range(len(prog.ops)))
    assert all(e[4]["target"] == "default" for e in regions)
    main = regions[0][0]
    for kind in ("dispatch", "sync", "place"):
        for e in events:
            if e[1] == f"repro:{kind}" and e[0] == main and "op" in e[4]:
                assert inside(e, regions[e[4]["op"]])
    exchanges = [e for e in events if e[1] == "repro:exchange"]
    assert {e[4]["op"] for e in exchanges} == {0, 1}   # the stencil ops
    assert all(e[0] == main for e in exchanges)
    # the worker waits on each exchange under the op it serves
    waits = [e for e in events if e[1] == "repro:sync" and e[0] != main]
    assert sorted(e[4]["op"] for e in waits) == [0, 1]
    led = sp.ledgers[0]
    assert led.syncs == sum(r.calls for r in led.regions.values()) == 5


@pytest.mark.parametrize("policy, syncs_per_call",
                         [(HostPolicy, 0), (DiscretePolicy, 1)])
def test_host_routed_replay_counts_its_sync(policy, syncs_per_call):
    """Single calls fall back to the Executor, which waits only for a
    staged call (DiscretePolicy), not for a host-routed one (HostPolicy);
    the replay waits for every op (and halo exchange) it records, on the
    host route or the device's."""
    prog, x = stencil_program(Ledger("host_spans"))
    sp = shard_program(prog, make_apu_mesh(1), policy(), overlap=False)
    host = sp.executor.host_ledger
    stencil, _, scale = (op.region for op in prog.ops)
    np.asarray(sp.run(scale, sp.run(stencil, sp.run(stencil, x))))
    calls = sum(r.calls for r in host.regions.values())
    assert calls == 3 and host.syncs == syncs_per_call * calls
    host.reset_timings()
    sp.replay(x)
    ledgers = (*sp.ledgers, host)
    assert sum(led.syncs for led in ledgers) == sum(
        r.calls for led in ledgers for r in led.regions.values()) >= 3


def test_every_program_span_goes_through_the_helper():
    """One way to open a span: ``TraceAnnotation`` appears only in the
    helper, and no program file names the benchmark's own spans."""
    for path in SRC.rglob("*.py"):
        text = path.read_text()
        assert "bench:" not in text, path
        if path.name != "ledger.py":
            assert not re.search(r"TraceAnnotation|TraceMe\(", text), path
