"""Benchmark harness main — one section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (deliverable d); ``--json <path>``
additionally writes a machine-readable report (per-section rows +
``ExecutionPlan`` summaries + the DSE sweep + replay calibration
artifacts registered via ``benchmarks.common``).  The full row/report
schema is documented in README.md §"The --json report schema".

Usage::

    python benchmarks/run.py [section ...] [--json out.json]
    python benchmarks/run.py --list

With no section arguments all sections run; otherwise only the named ones
(e.g. ``run.py bench_sim --json bench_sim.json``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

# Allow ``python benchmarks/run.py`` (not just ``python -m benchmarks.run``
# with PYTHONPATH=src): both the repo root and src/ must be importable.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_REPO_ROOT, os.path.join(_REPO_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _sections(points=None, workers=None, search=False, cache=None):
    import functools

    from benchmarks import (bench_decode, bench_dse, bench_kernels,
                            bench_pruning, bench_replay,
                            bench_rewrite_overlap, bench_serve, bench_shard,
                            bench_sim, bench_stream_modes, roofline)
    return [
        ("bench_stream_modes", "Fig6/Fig7 stream-mode comparison",
         bench_stream_modes.run),
        ("bench_pruning", "Token pruning (paper SI claim)",
         bench_pruning.run),
        ("bench_rewrite_overlap", "TranCIM rewrite-latency analysis",
         bench_rewrite_overlap.run),
        ("bench_sim", "StreamDCIM simulator (three-way + SI stall)",
         bench_sim.run),
        ("dse", "Design-space exploration (energy/latency Pareto + knee)",
         functools.partial(bench_dse.run, points=points, workers=workers,
                           search=search, cache=cache)),
        ("replay", "Plan/trace replay + calibration (record real kernels)",
         bench_replay.run),
        ("serve", "Continuous-batching serving (engine vs simulate_serve)",
         bench_serve.run),
        ("shard", "Chiplet-mesh scale-out (speedup-vs-chips, NoC model)",
         bench_shard.run),
        ("bench_decode", "Decode regime (tile-stream latency win)",
         bench_decode.run),
        ("bench_kernels", "Kernel micro-benchmarks", bench_kernels.run),
        ("roofline", "Roofline summary (from dry-run artifacts)",
         roofline.run),
    ]


def _parse_row(row: str) -> dict:
    """Split a ``name,us_per_call,derived`` CSV row (derived may itself
    contain commas) into a JSON-ready record."""
    parts = row.split(",", 2)
    rec = {"name": parts[0]}
    if len(parts) > 1:
        try:
            rec["us_per_call"] = float(parts[1])
        except ValueError:
            rec["us_per_call"] = parts[1]
    if len(parts) > 2:
        rec["derived"] = parts[2]
    return rec


def main(argv=None) -> None:
    from repro.core.compile_cache import use_persistent_cache
    use_persistent_cache()
    ap = argparse.ArgumentParser(
        prog="benchmarks/run.py",
        description="StreamDCIM repro benchmark harness")
    ap.add_argument("sections", nargs="*",
                    help="section names to run (default: all)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write a machine-readable JSON report "
                         "(rows + ExecutionPlan summaries + DSE sweep)")
    ap.add_argument("--points", type=int, metavar="N", default=None,
                    help="design-point budget for the dse section "
                         "(presets first; CI smoke)")
    ap.add_argument("--workers", type=int, metavar="N", default=None,
                    help="process-pool width for the dse sweep "
                         "(rows byte-identical to serial; DESIGN.md §16)")
    ap.add_argument("--search", action="store_true",
                    help="run the dse section as a successive-halving "
                         "frontier search instead of the exhaustive "
                         "grid (DESIGN.md §16)")
    ap.add_argument("--cache", metavar="DIR", default=None,
                    help="on-disk simulation cache for the dse section "
                         "— repeat runs warm-start (DESIGN.md §16)")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="cProfile each section into DIR: raw pstats "
                         "(<section>.pstats) + a top-20 cumulative text "
                         "summary (<section>.txt)")
    ap.add_argument("--perfetto", metavar="DIR", default=None,
                    help="dump Perfetto trace_event timelines registered "
                         "by the sections that ran (sim/serve/dse/replay) "
                         "into DIR — open at https://ui.perfetto.dev")
    ap.add_argument("--baseline", metavar="DIR", default=None,
                    help="write schema-versioned BENCH_<section>.json "
                         "snapshots for the sections that ran into DIR "
                         "(commit them to start/refresh the perf "
                         "trajectory)")
    ap.add_argument("--check-baseline", metavar="DIR", default=None,
                    dest="check_baseline",
                    help="compare this run's bench snapshots against the "
                         "committed baselines in DIR (tolerance bands, "
                         "direction-aware); exit 1 on any regression — "
                         "the `make bench-check` CI gate")
    ap.add_argument("--list", action="store_true", dest="list_sections",
                    help="print available sections and exit")
    args = ap.parse_args(argv)

    sections = _sections(points=args.points, workers=args.workers,
                         search=args.search, cache=args.cache)
    if args.list_sections:
        for key, title, _ in sections:
            print(f"{key:24s} {title}")
        return

    if args.sections:
        known = {key for key, _, _ in sections}
        unknown = [w for w in args.sections if w not in known]
        if unknown:
            print(f"unknown section(s) {unknown}; available: {sorted(known)}",
                  file=sys.stderr)
            sys.exit(2)
        sections = [s for s in sections if s[0] in args.sections]

    from benchmarks import common
    common.reset_plan_log()

    report = {"schema_version": common.REPORT_SCHEMA_VERSION,
              "command": "benchmarks/run.py " + " ".join(args.sections),
              "metadata": common.run_metadata(),
              "sections": [], "plans": []}
    if args.profile:
        os.makedirs(args.profile, exist_ok=True)

    print("name,us_per_call,derived")
    failed = 0
    for key, title, fn in sections:
        print(f"# --- {title} ---")
        sec = {"name": key, "title": title, "ok": True, "rows": []}
        try:
            if args.profile:
                # Receipts for hot-path claims: raw pstats for pstats/
                # snakeviz plus a human-readable top-20 cumulative dump.
                import cProfile
                import io
                import pstats
                prof = cProfile.Profile()
                out = prof.runcall(fn)
                pstats_path = os.path.join(args.profile, f"{key}.pstats")
                prof.dump_stats(pstats_path)
                buf = io.StringIO()
                stats = pstats.Stats(prof, stream=buf)
                stats.sort_stats("cumulative").print_stats(20)
                with open(os.path.join(args.profile, f"{key}.txt"),
                          "w") as f:
                    f.write(buf.getvalue())
                print(f"# profile -> {pstats_path}", file=sys.stderr)
            else:
                out = fn()
            for row in out:
                print(row)
                sec["rows"].append(_parse_row(row))
        except Exception:  # noqa: BLE001
            failed += 1
            sec["ok"] = False
            sec["error"] = traceback.format_exc()
            print(f"# SECTION FAILED: {title}")
            traceback.print_exc()
        report["sections"].append(sec)

    if args.json:
        report["plans"] = [p.summary() for p in common.PLAN_LOG]
        if common.SEARCH_LOG:
            # The search artifact (DESIGN.md §16): the survivors' full
            # sweep plus the per-rung elimination ledger — supersedes
            # the plain dse block for a --search run (CI uploads this).
            report["search"] = common.SEARCH_LOG[-1].to_dict()
        elif common.DSE_LOG:
            report["dse"] = common.DSE_LOG[-1].to_dict()
        if common.SERVE_LOG:
            # The serving artifact (DESIGN.md §11): the engine's executed
            # timeline next to the simulator's — per-step records carry
            # predicted vs simulated decode HBM bytes (CI uploads this).
            report["serve"] = [
                {"engine": eng.stats(), "sim": sim.to_dict()}
                for eng, sim in common.SERVE_LOG]
        if common.SHARD_LOG:
            # The scale-out artifact (DESIGN.md §13): speedup-vs-chips
            # curves + per-row serialized ShardedPlans (CI uploads this).
            report["shard"] = common.SHARD_LOG[-1].to_dict()
        if common.REPLAY_LOG:
            # The calibration artifact (DESIGN.md §10): one entry per
            # recorded model — the fitted CalibrationReport plus the
            # traced plan JSON that replays it (CI uploads this).
            report["replay"] = [
                {"calibration": rep.to_dict(),
                 "traced_ops": list(plan.traced_ops),
                 "plan_json": plan.to_json()}
                for plan, rep in common.REPLAY_LOG]
        if common.BENCH_LOG:
            # The perf-tracking block (DESIGN.md §14): per-section
            # gating metrics + critical-path summaries, same shape the
            # BENCH_<section>.json baselines commit.
            from benchmarks import history
            report["bench"] = {
                sec: history.snapshot(sec, entry,
                                      metadata=common.run_metadata()
                                      ).to_dict()
                for sec, entry in sorted(common.BENCH_LOG.items())}
        report["ok"] = failed == 0
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"# json report -> {args.json}", file=sys.stderr)

    if args.perfetto:
        from repro.obs.timeline import validate_timeline, write_timeline
        os.makedirs(args.perfetto, exist_ok=True)
        for name, thunk in common.TIMELINE_LOG:
            tl = thunk()
            validate_timeline(tl)
            stem = "".join(c if c.isalnum() or c in "-_." else "_"
                           for c in name)
            path = os.path.join(args.perfetto, f"{stem}.perfetto.json")
            write_timeline(tl, path)
            print(f"# perfetto timeline -> {path}", file=sys.stderr)
        if not common.TIMELINE_LOG:
            print("# --perfetto: no section registered a timeline",
                  file=sys.stderr)

    if args.baseline or args.check_baseline:
        from benchmarks import history
        if not common.BENCH_LOG:
            print("# no section registered bench metrics "
                  "(run bench_sim/serve/shard)", file=sys.stderr)
            sys.exit(2)

    if args.baseline:
        for sec, entry in sorted(common.BENCH_LOG.items()):
            snap = history.snapshot(sec, entry,
                                    metadata=common.run_metadata())
            path = history.write_snapshot(snap, args.baseline)
            print(f"# bench baseline -> {path}", file=sys.stderr)

    regressed = False
    if args.check_baseline:
        for sec, entry in sorted(common.BENCH_LOG.items()):
            snap = history.snapshot(sec, entry)
            path = history.baseline_path(args.check_baseline, sec)
            if not os.path.exists(path):
                print(f"# bench-check: no committed baseline {path} — "
                      f"run with --baseline first", file=sys.stderr)
                regressed = True
                continue
            cmp = history.compare(snap, history.load_snapshot(path))
            print(cmp.format())
            if not cmp.ok:
                regressed = True
        if regressed:
            print("# bench-check FAILED: perf regression against "
                  "committed baselines (re-baseline with --baseline "
                  "if intentional)", file=sys.stderr)

    if failed or regressed:
        sys.exit(1)


if __name__ == '__main__':
    main()
