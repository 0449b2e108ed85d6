"""Simulator-core scaling benchmark — first point of the perf trajectory.

Sweeps job count on the *scaled mixed cluster* (the ``repro.appdag``
mixed-cluster species — dense-DP training, pipelined serving and two
comm-normalized MapReduce templates — stamped out as a Poisson arrival
process on a 48-port fabric) across scheduling policies, and reports the
compacted core's wall time, events/sec and decision counts per (policy,
size).  The frozen pre-compaction core (``repro.core.simref``) is timed
on the sizes where it is tractable as the baseline, with a bit-exact
old-vs-new equivalence assert at the smallest size; the headline number
is the 500-job mixed MSA wall-clock speedup (ISSUE-3 gate: >= 5x).

Writes ``BENCH_sim_core.json``:

  rows[]                 one dict per (core, policy, jobs) measurement
  speedup_500_jobs_msa   reference wall / compacted wall at 500 jobs
  tracer_overhead        tracer-on vs tracer-off walls at the largest
                         MSA size <= 500 (repro.obs overhead contract:
                         results must stay bit-identical; the tracked
                         walls quantify the tracing cost)
  batched                the repro.core.simjax lockstep section
                         (``--batched``): per registered scenario, the
                         same N fifo seeds run numpy-sequentially vs as
                         one jitted batch, per-lane JCT/CCT agreement
                         asserted; headline is the 20-seed pipe_serve
                         lane (gated at >= 5x warm by check_batched);
                         ``device`` names the platform, device kind and
                         device count the engine ran on
  notes[]                anything skipped or capped (no silent caps)

All wall times come from ``time.perf_counter()``.

Usage:
  PYTHONPATH=src python benchmarks/perf_sim_core.py [--out PATH]
      [--sizes N ...] [--policies NAME ...] [--seed N] [--smoke]
      [--topology SPEC] [--overhead-only] [--batched [--batched-seeds N]]

``--overhead-only`` runs just the tracer-overhead pair (one traced +
one untraced run at the largest requested MSA size) and merges the
``tracer_overhead`` section into an existing ``--out`` document, so the
tracked number is refreshable without re-running the full sweep.

``--smoke`` is the CI profile: tiny sizes, baseline only at the smallest,
per-link ``debug_checks`` on, then validates the emitted JSON and exits
non-zero on any check failure.  ``--topology`` (any
``repro.core.make_topology`` spec) runs the sweep on a routed topology;
every row is tagged with its topology so the ``BENCH_sim_core.json``
trajectory stays comparable across specs, and the pre-topology reference
core (big-switch only) is skipped with a note.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import random

from repro.appdag.mixer import (FB_WIDE_STREAM, _fb_templates,
                                mixed_templates, poisson_mix)
from repro.core import (Fabric, RunResult, available_policies,
                        make_scheduler, make_topology, simulate)
from repro.core.simref import simulate_reference
from repro.experiments import topology_arg

N_PORTS = 48
SIZES = (50, 200, 500, 2000)
POLICIES = ("msa", "varys", "fifo", "fair", "cpath")
# Reference-core runs: the old core is O(total flows) per event, so the
# sweep caps it at 500 jobs (a 2000-job reference run takes hours — the
# regime this rebuild exists to escape); MSA is the acceptance policy,
# varys rides along for a second ordered-policy data point.
BASELINE = {"msa": (50, 200, 500), "varys": (50, 200)}
# The compacted core still sweeps 2000 jobs for the ordered policies;
# cpath re-keys every record of every live job per event (its critical
# paths track continuously-draining compute), so its 2000-job point is
# skipped rather than silently capped — see the JSON notes.
COMPACT_CAP = {"cpath": 500}


def scale_mixed(n_jobs: int, seed: int = 0, n_ports: int = N_PORTS):
    """Fresh jobs for one run: the mixed-cluster species plus a wider
    MapReduce tail (the FB trace's heavy tail runs to 100-wide coflows;
    the 24-port scenario caps spans at 12, this 48-port fabric admits
    spans up to half the fabric), constant arrival rate per job (a
    steady stream, not a burst), random placement."""
    templates = list(mixed_templates(seed))
    train = templates[0].dag
    rng = random.Random(seed + FB_WIDE_STREAM)
    templates += _fb_templates(rng, 2, max_span=n_ports // 2,
                               target_size=train.total_size())
    train_load = train.total_load()
    jobs = poisson_mix(templates, n_jobs, n_ports,
                       mean_interarrival=0.15 * train_load, seed=seed)
    return n_ports, jobs


def _run_one(core: str, pname: str, n_jobs: int, seed: int,
             topology: str = "big_switch",
             debug_checks: bool = False) -> dict:
    n_ports, jobs = scale_mixed(n_jobs, seed=seed)
    sched = make_scheduler(pname)
    t0 = time.perf_counter()
    if core == "compacted":
        fabric = Fabric(topology=make_topology(topology, n_ports))
        res = simulate(jobs, sched, fabric=fabric,
                       debug_checks=debug_checks)
    else:
        res = simulate_reference(jobs, sched, n_ports=n_ports)
    wall = time.perf_counter() - t0
    rr = RunResult.from_sim(res, wall_s=wall)
    if rr.n_jobs != n_jobs:
        raise AssertionError(f"{core}/{pname}/{n_jobs}: incomplete run")
    return {"core": core, "policy": pname, "jobs": n_jobs,
            "topology": topology, **rr.perf_row()}


def measure_tracer_overhead(pname: str, n_jobs: int, seed: int,
                            topology: str = "big_switch",
                            off_row: dict | None = None) -> dict:
    """Tracer-on vs tracer-off wall time at one (policy, size) point.

    The untraced measurement can be reused from an already-measured row
    (``off_row``); the traced run attaches a ``repro.obs.MemoryTracer``
    and must reproduce the untraced ``avg_jct`` bit-identically (the
    overhead contract — validated by ``check``)."""
    from repro.obs import MemoryTracer

    if off_row is None:
        off_row = _run_one("compacted", pname, n_jobs, seed,
                           topology=topology)
    n_ports, jobs = scale_mixed(n_jobs, seed=seed)
    tracer = MemoryTracer()
    fabric = Fabric(topology=make_topology(topology, n_ports))
    t0 = time.perf_counter()
    res = simulate(jobs, make_scheduler(pname), fabric=fabric, tracer=tracer)
    wall_on = time.perf_counter() - t0
    wall_off = float(off_row["wall_s"])
    return {"policy": pname, "jobs": n_jobs, "topology": topology,
            "wall_off_s": round(wall_off, 3),
            "wall_on_s": round(wall_on, 3),
            "overhead_pct": round((wall_on / wall_off - 1.0) * 100, 1)
            if wall_off > 0 else 0.0,
            "n_trace_events": len(tracer.events),
            "avg_jct_bit_equal": res.avg_jct == off_row["avg_jct"]}


#: Tolerance for batched-vs-numpy per-lane JCT/CCT agreement.  The JAX
#: engine is not bit-exact (XLA reorders float accumulations); observed
#: divergence on the registered scenarios is <= ~1e-12 seconds.
BATCHED_TOL = 1e-6


def run_batched_bench(seeds: int, scenarios=None, smoke: bool = False) -> dict:
    """The DESIGN.md §17 lockstep-engine measurement: for each registered
    scenario, run the same ``seeds`` fifo instances (a) sequentially on
    the numpy core and (b) as one ``repro.core.simjax`` batch, assert
    per-lane JCT/CCT agreement within ``BATCHED_TOL``, and record both
    the warm (steady-state) and cold (compile-inclusive — one XLA trace
    is shared by all lanes) batched walls.  The headline is the
    pipe_serve lane: the paper's headline scenario and the shape where
    the batched step is cheapest relative to numpy's per-event cost."""
    import jax

    from repro.appdag.mixer import SCENARIOS, build_scenario
    from repro.core.simjax import pack_instance, run_fifo_batch

    names = sorted(scenarios if scenarios is not None else SCENARIOS)
    rows: list[dict] = []
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    notes = [f"walls are single-process wall-clock with the engine on "
             f"{dev.platform} ({dev.device_kind}, {device['count']} "
             "device(s)); cold includes the jit trace + compile (or its "
             "persistent-cache load), amortized over all "
             f"{seeds} lanes by the shared padded batch shape"]
    for name in names:
        cells = [build_scenario(name, seed=s, lint=False)
                 for s in range(seeds)]
        lanes = [pack_instance(fab, jobs) for fab, jobs in cells]
        t0 = time.perf_counter()
        batched = run_fifo_batch(lanes)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        batched = run_fifo_batch(lanes)
        warm = time.perf_counter() - t0
        t0 = time.perf_counter()
        seq = [simulate(jobs, make_scheduler("fifo"), fabric=fab)
               for fab, jobs in cells]
        seq_wall = time.perf_counter() - t0
        diff = 0.0
        for lane, ref in zip(batched, seq):
            for jname, jct in ref.jct.items():
                diff = max(diff, abs(lane.jct[jname] - jct))
            for jname, cct in ref.cct.items():
                diff = max(diff, abs(lane.cct[jname] - cct))
        row = {"scenario": name, "lanes": seeds,
               "numpy_seq_s": round(seq_wall, 3),
               "batched_cold_s": round(cold, 3),
               "batched_warm_s": round(warm, 3),
               "speedup_warm": round(seq_wall / warm, 2),
               "speedup_cold": round(seq_wall / cold, 2),
               "max_abs_jct_diff": diff,
               "max_lane_events": max(r.events for r in batched),
               "flows_padded": max(p.flow_node.size for p in lanes)}
        rows.append(row)
        print(f"  batched   fifo   {name:<20} numpy {seq_wall:6.2f}s  "
              f"warm {warm:6.2f}s  cold {cold:6.2f}s  "
              f"({row['speedup_warm']:.2f}x warm)", flush=True)
    out = {"engine": "repro.core.simjax", "policy": "fifo",
           "device": device, "seeds": seeds, "rows": rows, "notes": notes}
    headline = next((r for r in rows if r["scenario"] == "pipe_serve"), None)
    if headline is not None:
        out["headline_scenario"] = "pipe_serve"
        # The gated headline is defined at 20 lanes; a 3-lane smoke (or
        # a custom --batched-seeds) must not masquerade as it.
        if headline["lanes"] == 20:
            out["speedup_batched_fifo_20seed"] = headline["speedup_warm"]
    elif not smoke:
        notes.append("pipe_serve not in scenario set: no headline speedup")
    return out


def _assert_equivalent(pname: str, n_jobs: int, seed: int) -> None:
    n_ports, jobs = scale_mixed(n_jobs, seed=seed)
    new = simulate(jobs, make_scheduler(pname), n_ports=n_ports)
    n_ports, jobs = scale_mixed(n_jobs, seed=seed)
    old = simulate_reference(jobs, make_scheduler(pname), n_ports=n_ports)
    if not (new.jct == old.jct and new.cct == old.cct
            and new.mf_service_order == old.mf_service_order):
        raise AssertionError(
            f"compacted core diverged from reference ({pname}, {n_jobs} jobs)")


def run_bench(sizes, policies, baseline, seed: int,
              equivalence_at: int | None, topology: str = "big_switch",
              debug_checks: bool = False) -> dict:
    rows: list[dict] = []
    notes: list[str] = []
    if topology != "big_switch":
        # The frozen pre-topology core only models the big switch.
        if baseline or equivalence_at is not None:
            notes.append(f"reference core skipped: topology {topology} "
                         "predates it (big-switch only)")
        baseline = {}
        equivalence_at = None
    if equivalence_at is not None:
        for pname in policies:
            _assert_equivalent(pname, equivalence_at, seed)
        notes.append(f"old-vs-new asserted bit-identical at "
                     f"{equivalence_at} jobs for {','.join(policies)}")
    capped: list[str] = []
    for n_jobs in sizes:
        for pname in policies:
            cap = COMPACT_CAP.get(pname)
            if cap is not None and n_jobs > cap:
                capped.append(f"{pname}@{n_jobs}")
                continue
            row = _run_one("compacted", pname, n_jobs, seed,
                           topology=topology, debug_checks=debug_checks)
            rows.append(row)
            print(f"  compacted {pname:<6} {n_jobs:>5} jobs  "
                  f"{row['wall_s']:>8.2f}s  {row['events_per_s']:>8.1f} ev/s",
                  flush=True)
    if capped:
        notes.append("compacted core skipped (policy re-keys every live "
                     "job per event, intractable at this size): "
                     + ", ".join(capped))
    for pname, bsizes in baseline.items():
        if pname not in policies:
            continue
        for n_jobs in bsizes:
            if n_jobs not in sizes:
                continue
            row = _run_one("reference", pname, n_jobs, seed)
            rows.append(row)
            print(f"  reference {pname:<6} {n_jobs:>5} jobs  "
                  f"{row['wall_s']:>8.2f}s  {row['events_per_s']:>8.1f} ev/s",
                  flush=True)
    skipped = [(p, s) for p, bs in baseline.items() if p in policies
               for s in sizes if s not in bs]
    if skipped:
        notes.append("reference core not run (intractable at scale) for: "
                     + ", ".join(f"{p}@{s}" for p, s in skipped))
    wall = {(r["core"], r["policy"], r["jobs"]): r["wall_s"] for r in rows}
    out = {
        "bench": "sim_core",
        "scenario": "scale_mixed (appdag train/serve + FB MapReduce)",
        "fabric_ports": N_PORTS,
        "topology": topology,
        "seed": seed,
        "rows": rows,
        "notes": notes,
    }
    ref = wall.get(("reference", "msa", 500))
    new = wall.get(("compacted", "msa", 500))
    if ref and new:
        out["speedup_500_jobs_msa"] = round(ref / new, 2)
    # Tracer overhead at the largest already-measured MSA point (the
    # repro.obs contract: bit-identical results, tracked extra wall).
    # Lives outside rows[] so the regression gate's row-key universe is
    # unchanged.
    opname = "msa" if "msa" in policies else policies[0]
    ocap = COMPACT_CAP.get(opname)
    osizes = [s for s in sizes if s <= 500 and (ocap is None or s <= ocap)]
    okey = ("compacted", opname, max(osizes)) if osizes else None
    if okey in wall:
        off_row = next(r for r in rows
                       if (r["core"], r["policy"], r["jobs"]) == okey)
        ov = measure_tracer_overhead(opname, okey[2], seed,
                                     topology=topology, off_row=off_row)
        out["tracer_overhead"] = ov
        print(f"  tracer    {opname:<6} {okey[2]:>5} jobs  "
              f"{ov['wall_on_s']:>8.2f}s traced vs {ov['wall_off_s']:.2f}s "
              f"({ov['overhead_pct']:+.1f}%)", flush=True)
    return out


def check(doc: dict, smoke: bool) -> list[str]:
    """Validity gates (the CI smoke job runs these on the emitted JSON)."""
    errs = []
    if not doc.get("rows"):
        errs.append("no rows emitted")
    for r in doc.get("rows", ()):
        for key in ("core", "policy", "jobs", "topology", "wall_s",
                    "events", "events_per_s", "sched_full", "sched_refresh"):
            if key not in r:
                errs.append(f"row missing {key}: {r}")
                break
        else:
            if not (r["events"] > 0 and r["events_per_s"] > 0):
                errs.append(f"degenerate row: {r}")
    if not smoke and "speedup_500_jobs_msa" in doc \
            and doc["speedup_500_jobs_msa"] < 5.0:
        errs.append(f"500-job mixed MSA speedup "
                    f"{doc['speedup_500_jobs_msa']}x < 5x (ISSUE-3 gate)")
    ov = doc.get("tracer_overhead")
    if ov is not None and not ov.get("avg_jct_bit_equal"):
        errs.append(f"traced run diverged from untraced "
                    f"({ov.get('policy')}@{ov.get('jobs')}): tracing must "
                    "be observational")
    bt = doc.get("batched")
    if bt is not None:
        errs.extend(check_batched(bt, smoke))
    return errs


def check_batched(bt: dict, smoke: bool) -> list[str]:
    """Validity gates for the ``batched`` section alone (the --batched
    path merges into a possibly-older document, so it must not re-judge
    rows it didn't produce)."""
    errs = []
    if not bt.get("rows"):
        errs.append("batched section has no rows")
    for r in bt.get("rows", ()):
        if r.get("max_abs_jct_diff", BATCHED_TOL) >= BATCHED_TOL:
            errs.append(f"batched engine diverged from numpy on "
                        f"{r.get('scenario')}: max |JCT/CCT diff| "
                        f"{r.get('max_abs_jct_diff')} >= {BATCHED_TOL}")
    if not smoke:
        sp = bt.get("speedup_batched_fifo_20seed")
        if sp is None:
            errs.append("batched section missing the 20-seed fifo "
                        "headline speedup")
        elif sp < 5.0:
            errs.append(f"20-seed fifo batched speedup {sp}x < 5x "
                        "(ISSUE-10 gate, pipe_serve lane)")
    return errs


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="output JSON (default: BENCH_sim_core.json, or "
                         "BENCH_sim_core_<topology>.json off big-switch "
                         "so routed sweeps never clobber the big-switch "
                         "trajectory baseline)")
    ap.add_argument("--sizes", type=int, nargs="+", default=None)
    ap.add_argument("--policies", nargs="+", default=None,
                    choices=available_policies(), metavar="NAME")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI profile: tiny sizes, per-link debug checks, "
                         "validate JSON, exit 1 on check failure")
    ap.add_argument("--topology", default="big_switch", metavar="SPEC",
                    type=topology_arg,
                    help="network topology spec (big_switch, "
                         "leaf_spine_<R>to1, fat_tree); non-big-switch "
                         "sweeps skip the pre-topology reference core")
    ap.add_argument("--overhead-only", action="store_true",
                    help="measure just the tracer overhead pair and merge "
                         "the tracer_overhead section into --out (keeps "
                         "the rest of an existing trajectory document)")
    ap.add_argument("--batched", action="store_true",
                    help="measure the repro.core.simjax lockstep engine "
                         "(DESIGN.md §17): every registered scenario x "
                         "--batched-seeds fifo lanes, numpy-sequential vs "
                         "one batch, equivalence asserted; merges the "
                         "'batched' section into --out")
    ap.add_argument("--batched-seeds", type=int, default=20, metavar="N",
                    help="lanes per scenario for --batched (default 20, "
                         "the tracked artifact's profile)")
    args = ap.parse_args()

    if args.smoke:
        sizes = tuple(args.sizes or (20, 50))
        policies = tuple(args.policies or ("msa", "varys", "fair"))
        baseline = {"msa": (sizes[0],)}
        equivalence_at = sizes[0]
    else:
        sizes = tuple(args.sizes or SIZES)
        policies = tuple(args.policies or POLICIES)
        baseline = BASELINE
        equivalence_at = min(sizes)

    if args.out is None:
        args.out = ("BENCH_sim_core.json" if args.topology == "big_switch"
                    else f"BENCH_sim_core_{args.topology}.json")

    if args.batched:
        from repro.core.simjax import place_compile_cache

        place_compile_cache()
        seeds = 3 if args.smoke and args.batched_seeds == 20 \
            else args.batched_seeds
        scen = ("pipe_serve", "mixed") if args.smoke else None
        bt = run_batched_bench(seeds, scenarios=scen, smoke=args.smoke)
        try:
            with open(args.out) as fh:
                doc = json.load(fh)
        except (OSError, json.JSONDecodeError):
            doc = {"bench": "sim_core", "rows": [], "notes": []}
        doc["batched"] = bt
        with open(args.out, "w") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"merged batched section into {args.out}")
        if "speedup_batched_fifo_20seed" in bt:
            print(f"20-seed fifo batched speedup (pipe_serve): "
                  f"{bt['speedup_batched_fifo_20seed']}x")
        errs = check_batched(bt, smoke=args.smoke)
        for e in errs:
            print(f"CHECK-FAIL[sim_core]: {e}", file=sys.stderr)
        sys.exit(1 if errs else 0)

    if args.overhead_only:
        pname = "msa" if "msa" in policies else policies[0]
        cap = COMPACT_CAP.get(pname)
        cands = [s for s in sizes if s <= 500 and (cap is None or s <= cap)]
        if not cands:
            print("CHECK-FAIL[sim_core]: no tractable size for "
                  "--overhead-only", file=sys.stderr)
            sys.exit(1)
        n_jobs = max(cands)
        ov = measure_tracer_overhead(pname, n_jobs, args.seed,
                                     topology=args.topology)
        try:
            with open(args.out) as fh:
                doc = json.load(fh)
        except (OSError, json.JSONDecodeError):
            doc = {"bench": "sim_core", "rows": [], "notes": []}
        doc["tracer_overhead"] = ov
        with open(args.out, "w") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"tracer overhead {pname}@{n_jobs}: {ov['wall_on_s']}s traced "
              f"vs {ov['wall_off_s']}s untraced ({ov['overhead_pct']:+.1f}%)")
        print(f"merged tracer_overhead into {args.out}")
        if not ov["avg_jct_bit_equal"]:
            print("CHECK-FAIL[sim_core]: traced run diverged from untraced",
                  file=sys.stderr)
            sys.exit(1)
        return

    doc = run_bench(sizes, policies, baseline, args.seed, equivalence_at,
                    topology=args.topology, debug_checks=args.smoke)
    with open(args.out, "w") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
        fh.write("\n")
    print(f"wrote {args.out}")
    if "speedup_500_jobs_msa" in doc:
        print(f"500-job mixed MSA speedup: {doc['speedup_500_jobs_msa']}x")

    with open(args.out) as fh:       # validate what actually landed on disk
        errs = check(json.load(fh), smoke=args.smoke)
    for e in errs:
        print(f"CHECK-FAIL[sim_core]: {e}", file=sys.stderr)
    if errs:
        sys.exit(1)


if __name__ == "__main__":
    main()
