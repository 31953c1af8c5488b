"""The paper's case study: simpleFoam on a lid-driven cavity, executed by
all three memory models (host / discrete-managed / unified) plus the
beyond-paper adaptive policy, with the coverage + migration report
(paper Figs 4-6).  A SyncedExecutor waits for every region call, so the
staging fraction is a share of device-inclusive seconds.

    PYTHONPATH=src python examples/cfd_cavity.py [--grid 20]
"""
import argparse

from repro.cfd.grid import Grid
from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
from repro.core.regions import (AdaptivePolicy, DiscretePolicy, HostPolicy,
                                SyncedExecutor, UnifiedPolicy)

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", type=int, default=16)
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args()
    cfg = SimpleConfig(grid=Grid((args.grid,) * 3), nu=0.1, inner_max=25)
    foms = {}
    policies = (("host", HostPolicy()), ("discrete", DiscretePolicy()),
                ("unified", UnifiedPolicy()),
                ("adaptive", AdaptivePolicy(cutoff=1024)))
    for name, policy in policies:
        app = SimpleFoam(cfg, executor=SyncedExecutor(policy))
        st = init_state(cfg)
        st, _, _ = app.run_steps(st, 1)          # warm compile caches
        app.ledger.reset_timings()
        st, fom, m = app.run_steps(st, args.steps)
        foms[name] = fom
        rep = app.ex.report()
        print(f"[{name:8s}] FOM {fom:.4f} s/step  "
              f"staging {rep['staging_fraction']*100:5.1f}%  "
              f"offloaded regions {rep['offloaded_regions']}/{rep['regions']}  "
              f"routing {rep['device_calls']}dev/{rep['host_calls']}host  "
              f"res_u {m['res_u']:.2e}")
    print(f"\nunified speedup vs discrete-managed: "
          f"x{foms['discrete']/foms['unified']:.2f}  (paper Fig 5: 4-5x)")
