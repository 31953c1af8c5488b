"""Incremental-acceleration ledger (paper C2, Figs 2 & 4).

The paper's porting method: walk a production code region by region, add one
directive per parallelizable loop, and track how much of a time-step executes
on the device. This module is that bookkeeping: every ``@offload_region`` is
registered; executors report where each call actually ran and how much
staging it cost; ``coverage_report()`` reproduces the Fig 2 (partial,
PETSc-style) vs Fig 4 (directive, near-total) comparison.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax


#: prefix of every host span the program opens (:func:`span`)
SPAN_PREFIX = "repro:"
#: span kinds: what the host is doing inside the span.  ``step`` is one
#: SIMPLE iteration, ``solve`` one linear solve, ``region`` one region call
#: (routing, placement, bookkeeping and what follows); inside a region
#: call ``dispatch`` enqueues the compiled executable, ``sync`` waits for
#: the device, ``place`` reshards operands or results, ``exchange``
#: dispatches a halo exchange and ``exchange_wait`` waits for the worker
#: thread that waits on one; ``readback`` copies a device scalar to the
#: host.
STEP = "step"
SOLVE = "solve"
REGION = "region"
DISPATCH = "dispatch"
SYNC = "sync"
READBACK = "readback"
EXCHANGE = "exchange"
EXCHANGE_WAIT = "exchange_wait"
PLACE = "place"


def span(kind: str, name: Optional[str] = None, **ids):
    """A host span ``repro:<kind>[:<name>]`` in the profiler's trace, on
    the device trace's clock; ``ids`` become the event's stats (``op``, the
    index of the op in a replay, shared by every span of that op on any
    thread; ``impl``/``target`` on region spans, or set later with
    ``set_metadata``).  Inert unless a profiler session runs.  ``#`` opens
    the stats in a TraceMe name, so a uniquified region name (``dot#2``) is
    written ``dot_2``."""
    label = SPAN_PREFIX + kind
    if name is not None:
        label += ":" + name.replace("#", "_")
    return jax.profiler.TraceAnnotation(label, **ids)


def readback(x, ledger: Optional["Ledger"] = None) -> float:
    """Copy a device scalar to the host as a float, inside a ``readback``
    span, and count it on ``ledger`` (if given)."""
    with span(READBACK):
        value = float(x)
    if ledger is not None:
        ledger.readbacks += 1
    return value


def variant_cell(target: str, bucket: int) -> str:
    """Key of one autotune calibration cell: routing target x size bucket
    (power-of-two: bucket ``b`` covers sizes in ``[2^(b-1), 2^b)``)."""
    return f"{target}@2^{bucket}"


@dataclasses.dataclass
class RegionRecord:
    name: str
    offloaded: bool = True              # does this region carry a directive?
    calls: int = 0
    device_calls: int = 0
    host_calls: int = 0
    #: host-clock seconds of the calls.  A call the executor waited on
    #: (a staged call, a replay's op) counts until the device finished
    #: it; an unstaged ``Executor.run`` call does not wait, and counts
    #: only the host's routing, placement and dispatch.  A
    #: ``SyncedExecutor`` waits for every call; device seconds per region
    #: otherwise come from a device trace.
    compute_s: float = 0.0
    device_compute_s: float = 0.0       # compute split by routing side — a
    host_compute_s: float = 0.0         # region may mix under AdaptivePolicy
    staging_s: float = 0.0              # discrete-emulation copy time
    staging_bytes: int = 0
    overlap_s: float = 0.0              # staging/exchange hidden behind compute
    #                                     (async + sharded overlapped replay;
    #                                     <= staging_s + exchange_s)
    exchange_s: float = 0.0             # inter-APU halo/boundary traffic time
    exchange_bytes: int = 0             # (sharded replay; Infinity Fabric model)
    host_elems: int = 0                 # routing accounting (was DispatchStats)
    device_elems: int = 0
    cutoff: Optional[int] = None        # calibrated TARGET_CUT_OFF, if any
    #: calls per selected implementation variant ("ref", "pallas", ...) —
    #: the declare-variant dispatch record of paper C3's second half
    impl_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    #: autotune winners per (target, size-bucket) cell (see variant_cell);
    #: persisted like ``cutoff`` — survives reset_timings()
    calibrated_variants: Dict[str, str] = dataclasses.field(
        default_factory=dict)

    @property
    def impl(self) -> Optional[str]:
        """The dominant implementation this row ran (most calls), or None
        before any variant-resolved call was recorded."""
        if not self.impl_counts:
            return None
        return max(self.impl_counts, key=self.impl_counts.get)

    @property
    def total_s(self) -> float:
        """Wall-clock this row cost the replay.  Overlapped seconds ran
        *concurrently* with some region's compute, so counting them again
        would double-book the node: ``total = compute + staging + exchange
        - overlap`` (the invariant ``Ledger.merged`` reproduces node-wide;
        see docs/SCALING.md)."""
        return (self.compute_s + self.staging_s + self.exchange_s
                - self.overlap_s)

    @property
    def exposed_exchange_s(self) -> float:
        """Exchange seconds NOT hidden behind compute.  Overlap attributes
        to staging first (the async lookahead's claim), the remainder to
        exchange (the sharded overlapped schedule's claim)."""
        return self.exchange_s - max(0.0, self.overlap_s - self.staging_s)

    @property
    def offload_fraction(self) -> float:
        tot = self.host_elems + self.device_elems
        return self.device_elems / tot if tot else 0.0

    @property
    def overlap_fraction(self) -> float:
        """Fraction of this region's hideable time (staging + exchange)
        that actually ran concurrently with another region's compute
        (Fig 6 mitigation: prefetch overlap; docs/SCALING.md: halo
        overlap)."""
        hideable = self.staging_s + self.exchange_s
        return self.overlap_s / hideable if hideable else 0.0


class Ledger:
    def __init__(self, name: str = "default"):
        self.name = name
        self.regions: Dict[str, RegionRecord] = {}
        # serving-engine accounting (repro.serve): scheduler decisions land
        # here so coverage_report() carries the serve story next to the
        # region rows it is made of.  Counters sum on merge; gauges
        # (occupancy, high-water bytes) take the max.
        self.serve_counters: Dict[str, float] = {}
        self.serve_gauges: Dict[str, float] = {}
        # static-verifier accounting (repro.analysis): findings per rule
        # id plus per-severity totals.  Counters sum on merge and — like
        # cutoffs and calibrated variants — persist across reset_timings()
        # (verification happens once at capture, not per replay).
        self.analysis_counters: Dict[str, float] = {}
        # pools attached for byte-level accounting (paper C4): their live
        # PoolStats are snapshotted into every coverage_report()
        self._pools: Dict[str, object] = {}
        # host reads of a device value (readback()), and the
        # block_until_ready calls that happen on the region path (an
        # Executor waits only for a staged call): both per-run, like timings
        self.readbacks = 0
        self.syncs = 0

    def region(self, name: str, offloaded: bool = True) -> RegionRecord:
        if name not in self.regions:
            self.regions[name] = RegionRecord(name=name, offloaded=offloaded)
        return self.regions[name]

    def register(self, name: str, offloaded: bool = True) -> str:
        """Register a NEW region under a guaranteed-unique name.

        Two anonymous regions sharing ``fn.__name__`` used to merge silently
        into one record; registration now auto-uniquifies (``dot``, ``dot#2``,
        ...) so every region owns its own row in the coverage report.
        """
        unique = name
        k = 2
        while unique in self.regions:
            unique = f"{name}#{k}"
            k += 1
        self.regions[unique] = RegionRecord(name=unique, offloaded=offloaded)
        return unique

    def record(self, name: str, *, device: bool, compute_s: float,
               staging_s: float = 0.0, staging_bytes: int = 0,
               offloaded: bool = True, elems: int = 0,
               overlap_s: float = 0.0, exchange_s: float = 0.0,
               exchange_bytes: int = 0,
               impl: Optional[str] = None) -> None:
        r = self.region(name, offloaded)
        r.calls += 1
        if impl is not None:
            r.impl_counts[impl] = r.impl_counts.get(impl, 0) + 1
        r.device_calls += int(device)
        r.host_calls += int(not device)
        r.compute_s += compute_s
        r.staging_s += staging_s
        r.staging_bytes += staging_bytes
        r.overlap_s += min(overlap_s, staging_s + exchange_s)
        r.exchange_s += exchange_s
        r.exchange_bytes += exchange_bytes
        if device:
            r.device_compute_s += compute_s
            r.device_elems += elems
        else:
            r.host_compute_s += compute_s
            r.host_elems += elems

    def set_cutoff(self, name: str, cutoff: int) -> None:
        """Store a calibrated TARGET_CUT_OFF with the region it governs."""
        self.region(name).cutoff = cutoff

    # -- serving-engine accounting (repro.serve) -----------------------
    def serve_record(self, event: str, n: float = 1) -> None:
        """Count one scheduler decision (``admitted``, ``retired``,
        ``evicted``, ...) into the report's ``serve`` section."""
        self.serve_counters[event] = self.serve_counters.get(event, 0) + n

    def serve_gauge(self, key: str, value: float) -> None:
        """Record a level (slot occupancy, KV page high-water bytes).
        Gauges keep the maximum seen — every caller passes its own running
        value, the ledger keeps the peak."""
        self.serve_gauges[key] = max(self.serve_gauges.get(key, value), value)

    # -- static-verifier accounting (repro.analysis) -------------------
    def analysis_record(self, key: str, n: float = 1) -> None:
        """Count one static-verifier event (a finding per rule id, a
        ``findings_error``/``findings_warning`` total, a verified
        program) into the report's ``analysis`` section."""
        self.analysis_counters[key] = self.analysis_counters.get(key, 0) + n

    def attach_pool(self, name: str, pool) -> None:
        """Surface a pool's byte-level PoolStats in coverage_report()
        (``pools`` section).  Re-attaching under the same name replaces."""
        self._pools[name] = pool

    def set_calibrated_variant(self, name: str, target: str, bucket: int,
                               winner: str) -> None:
        """Store an autotuned variant winner for one (target, size-bucket)
        cell with the region it governs — the declare-variant analogue of
        :meth:`set_cutoff`."""
        r = self.region(name)
        r.calibrated_variants[variant_cell(target, bucket)] = winner

    def reset_timings(self) -> None:
        for r in self.regions.values():
            r.calls = r.device_calls = r.host_calls = 0
            r.compute_s = r.staging_s = r.overlap_s = 0.0
            r.exchange_s = 0.0
            r.staging_bytes = r.exchange_bytes = 0
            r.device_compute_s = r.host_compute_s = 0.0
            r.host_elems = r.device_elems = 0
            r.impl_counts = {}          # per-call record; calibrated_variants
            #                             and cutoff persist like settings
        self.serve_counters.clear()     # per-run accounting, like timings;
        self.serve_gauges.clear()       # attached pools persist like settings
        self.readbacks = self.syncs = 0
        # analysis_counters persist: verification is per capture, not per
        # run — resetting timings must not erase what the verifier found

    def merge_from(self, other: "Ledger") -> None:
        """Accumulate another ledger's rows into this one (rows matched by
        name).  This is the node-level aggregation of the sharded replay:
        per-device ledgers fold into one, and ``coverage_report()`` on the
        result is the node view."""
        for r in other.regions.values():
            m = self.region(r.name, r.offloaded)
            m.calls += r.calls
            m.device_calls += r.device_calls
            m.host_calls += r.host_calls
            m.compute_s += r.compute_s
            m.device_compute_s += r.device_compute_s
            m.host_compute_s += r.host_compute_s
            m.staging_s += r.staging_s
            m.staging_bytes += r.staging_bytes
            m.overlap_s += r.overlap_s
            m.exchange_s += r.exchange_s
            m.exchange_bytes += r.exchange_bytes
            m.host_elems += r.host_elems
            m.device_elems += r.device_elems
            for impl, n in r.impl_counts.items():
                m.impl_counts[impl] = m.impl_counts.get(impl, 0) + n
            for cell, winner in r.calibrated_variants.items():
                m.calibrated_variants.setdefault(cell, winner)
            if m.cutoff is None:
                m.cutoff = r.cutoff
        for k, v in other.serve_counters.items():
            self.serve_counters[k] = self.serve_counters.get(k, 0) + v
        for k, v in other.serve_gauges.items():
            self.serve_gauges[k] = max(self.serve_gauges.get(k, v), v)
        for k, v in other.analysis_counters.items():
            self.analysis_counters[k] = self.analysis_counters.get(k, 0) + v
        self.readbacks += other.readbacks
        self.syncs += other.syncs

    @classmethod
    def merged(cls, ledgers, name: str = "node") -> "Ledger":
        """A new ledger holding the row-wise sum of ``ledgers``."""
        out = cls(name)
        for l in ledgers:
            out.merge_from(l)
        return out

    def clear(self) -> None:
        """Drop all region rows. Long-lived processes that rebuild region
        programs against one shared ledger (auto-uniquified names grow it)
        should clear between generations — or give each app its own Ledger."""
        self.regions.clear()
        self.serve_counters.clear()
        self.serve_gauges.clear()
        self.analysis_counters.clear()
        self._pools.clear()
        self.readbacks = self.syncs = 0

    # ------------------------------------------------------------------
    def coverage_report(self) -> dict:
        # total_s subtracts overlap_s per row: seconds hidden behind compute
        # ran concurrently and must not be double-booked into the node wall
        # (invariant: total == compute + staging + exchange - overlap)
        total = sum(r.total_s for r in self.regions.values())
        # per-side compute, not whole rows: under adaptive routing one region
        # mixes host and device calls, and a single device call must not
        # re-attribute the row's host time (Fig 4 coverage would read ~1.0)
        dev = sum(r.device_compute_s for r in self.regions.values()
                  if r.offloaded)
        compute = sum(r.compute_s for r in self.regions.values())
        staging = sum(r.staging_s for r in self.regions.values())
        overlap = sum(r.overlap_s for r in self.regions.values())
        exchange = sum(r.exchange_s for r in self.regions.values())
        exposed_exchange = sum(r.exposed_exchange_s
                               for r in self.regions.values())
        hideable = staging + exchange
        host_calls = sum(r.host_calls for r in self.regions.values())
        device_calls = sum(r.device_calls for r in self.regions.values())
        host_elems = sum(r.host_elems for r in self.regions.values())
        device_elems = sum(r.device_elems for r in self.regions.values())
        elems = host_elems + device_elems
        impl_counts: Dict[str, int] = {}
        for r in self.regions.values():
            for impl, n in r.impl_counts.items():
                impl_counts[impl] = impl_counts.get(impl, 0) + n
        calibrated = {r.name: dict(r.calibrated_variants)
                      for r in self.regions.values()
                      if r.calibrated_variants}
        variant_wins: Dict[str, int] = {}
        for cells in calibrated.values():
            for winner in cells.values():
                variant_wins[winner] = variant_wins.get(winner, 0) + 1
        extra: Dict[str, dict] = {}
        if self.serve_counters or self.serve_gauges:
            # serving engine (repro.serve): scheduler counters + gauges
            extra["serve"] = {**self.serve_counters, **self.serve_gauges}
        if self.analysis_counters:
            # static verifier (repro.analysis): findings per rule id
            extra["analysis"] = dict(self.analysis_counters)
        if self._pools:
            # byte-level pool accounting (paper C4): live PoolStats snapshot
            pools = {}
            for pname, pool in self._pools.items():
                st = pool.stats.as_dict()
                fb = getattr(pool, "free_bytes", None)
                if fb is not None:
                    st["free_bytes"] = fb
                pools[pname] = st
            extra["pools"] = pools
        return {
            **extra,
            "regions": len(self.regions),
            "offloaded_regions": sum(1 for r in self.regions.values()
                                     if r.offloaded),
            "total_s": total,
            "compute_s": compute,
            "device_compute_s": dev,
            "staging_s": staging,
            "device_fraction": dev / total if total else 0.0,
            "staging_fraction": staging / total if total else 0.0,  # Fig 6
            # inter-APU boundary traffic (sharded replay, repro.core
            # .shard_program): explicit halo-exchange regions land their
            # seconds/bytes here, next to the compute and staging they
            # trade against — the Infinity Fabric split of the node report
            "exchange_s": exchange,
            "exchange_bytes": sum(r.exchange_bytes
                                  for r in self.regions.values()),
            # fraction of node wall that is EXPOSED exchange — overlapped
            # exchange seconds ran behind compute and are excluded (overlap
            # attributes to staging first, remainder to exchange)
            "exchange_fraction": exposed_exchange / total if total else 0.0,
            "exposed_exchange_s": exposed_exchange,
            # overlapped replay (async lookahead staging + sharded halo
            # overlap): how much of the hideable time (staging + exchange)
            # ran behind compute, and the staging seconds saved vs a fully
            # synchronous replay of the same program
            "overlap_s": overlap,
            "overlap_fraction": overlap / hideable if hideable else 0.0,
            "staging_saved_s": sum(min(r.overlap_s, r.staging_s)
                                   for r in self.regions.values()),
            # routing accounting (absorbed from dispatch.DispatchStats):
            # every host/device decision — static or TARGET_CUT_OFF-adaptive —
            # lands here, next to the staging fractions it trades against.
            "host_calls": host_calls,
            "device_calls": device_calls,
            "offload_elem_fraction": device_elems / elems if elems else 0.0,
            "cutoffs": {r.name: r.cutoff for r in self.regions.values()
                        if r.cutoff is not None},
            # declare-variant dispatch (repro.core.regions Selector axis):
            # which implementation each call actually ran, the autotuned
            # winner per (region, target, size-bucket) cell, and how many
            # cells each variant won across the whole calibration
            "impl_counts": impl_counts,
            "calibrated_variants": calibrated,
            "variant_wins": variant_wins,
            # host round trips: device scalars read back, and the region
            # calls waited for.  A call not waited for booked only the
            # host's seconds into compute_s, so with syncs < calls the
            # compute, total and fraction fields above are host time in
            # part; a SyncedExecutor waits for every call
            "readbacks": self.readbacks,
            "syncs": self.syncs,
        }

    def table(self) -> List[dict]:
        return [dataclasses.asdict(r) for r in self.regions.values()]


GLOBAL_LEDGER = Ledger()


def offload_region(name: Optional[str] = None, *, offloaded: bool = True,
                   ledger: Optional[Ledger] = None, **kw):
    """Deprecated alias for :func:`repro.core.regions.region`.

    Mark a function as one OpenMP-directive-sized region; the returned
    :class:`~repro.core.regions.Region` is jitted, self-times into the ledger,
    and can be re-routed (host/device/staged) by any executor without touching
    the function body — the "one line per loop" porting experience of
    listings 4-6. New code should import ``region`` from
    ``repro.core.regions`` directly.
    """
    from repro.core.regions import region
    return region(name, offloaded=offloaded, ledger=ledger, **kw)
