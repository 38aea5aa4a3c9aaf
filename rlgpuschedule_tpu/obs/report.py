"""Multihost merge + run post-mortem CLI.

``python -m rlgpuschedule_tpu.obs.report <obs-dir>`` merges every
per-rank event stream under ``<obs-dir>`` into one monotonic-ordered
timeline and prints the run's post-mortem:

- header: schema versions, emitting ranks, event count, time span;
- start-up table (``obs.startup``, from each ``run_start`` event's
  ``startup`` field): where the process's time went before its run
  began — imports, the backend's start, ``build`` by phase, tracing and
  lowering, compiling, cache loads, earlier run calls by loop section —
  and the programs that cost most;
- phase-time table (host wall seconds per run-loop phase, from the
  ``iteration`` spans);
- span tree (``--trace`` runs): per-phase self/child time from the
  flight recorder's nested ``span_begin``/``span_end`` extents, torn
  (crash-open) spans flagged; plus the MEASURED async actor/learner
  occupancy (``async_overlap_measured``) replacing PR 8's phase-sum
  projection;
- clock-skew annotation: with ≥2 sampled ranks the merged timeline is
  rewritten onto rank 0's corrected monotonic axis (``obs.skew``) and
  the per-rank offsets/residuals are reported;
- restart / rollback history: supervisor launch→failure→relaunch
  decisions, watchdog rollbacks, checkpoint save/restore/reject events
  and fault injections, in timeline order;
- steps/s curve (one row per logged iteration);
- chaos story (``env_fault`` events from ``evaluate --chaos``): the
  regime × scheduler degradation cells, in one table;
- flywheel & fleet health (ISSUE 20): promotion verdicts
  (``promote_blocked`` / ``promote_apply`` / ``promote_rollback``),
  serving-fleet lifecycle (``serve_fault`` / ``engine_eject`` /
  ``engine_readmit`` / ``serve_retry``) and SLO burn alerts
  (``slo_burn_alert`` / ``slo_burn_clear``), in timeline order;
- alarm summary (``recompile`` / ``transfer`` / ``slow_iteration``).

``--request ID`` switches to the single-request post-mortem: the
request id (as minted by the server or carried on the ``X-Request-Id``
header / v2 frame field) is joined across the serve instants
(``enqueue`` → ``served``/``shed``/``dispatch_failed``, with queue wait
and end-to-end latency from the dispatch record), the flight log
(``--flight-log`` — which sealed shard/row holds the logged decision
and its deadline outcome), and the promotion ledger in the same
directory (which canary verdicts replayed a window covering that row).
Exit 1 when the id appears nowhere.

Exit codes: 0 ok, 1 no events under the directory (an empty post-mortem
must fail loudly), 2 usage. ``--strict-alarms`` additionally exits 1
when any post-warmup alarm event fired — the CI hook: a geometry-stable
smoke run must produce a merged timeline with ZERO ``recompile`` events
(ci.sh smoke stage).
"""
from __future__ import annotations

import argparse
import json
import sys

from .events import merge_dir
from .skew import correct_events
from .trace import (SPAN_KINDS, async_overlap_summary, build_span_tree,
                    to_chrome_trace)

# event kinds that are production alarms (Alarms emissions; ``compile``
# is the blessed warmup/amnesty record, not an alarm)
ALARM_KINDS = ("recompile", "transfer", "slow_iteration")

# the restart/rollback/fault story, in one timeline
_HISTORY_KINDS = (
    "gang_launch", "rank_failure", "gang_restart", "gang_shrink",
    "supervisor_done", "rollback", "fault", "ckpt_reject",
    "ckpt_crc_reject", "ckpt_elastic_restore", "worker_resumed",
)

# the serving-fleet + flywheel story: promotion verdicts, engine
# lifecycle, SLO burn alerts (none are alarm kinds)
_FLEET_KINDS = (
    "promote_blocked", "promote_apply", "promote_rollback",
    "serve_fault", "engine_eject", "engine_readmit", "serve_retry",
    "slo_burn_alert", "slo_burn_clear",
)


def build_report(events: list[dict]) -> dict:
    """Aggregate a merged timeline into the post-mortem's sections."""
    ranks = sorted({e.get("rank", 0) for e in events})
    versions = sorted({e.get("v", 0) for e in events})
    monos = [e["mono"] for e in events if "mono" in e]
    span_s = (max(monos) - min(monos)) if monos else 0.0
    t0 = min(monos) if monos else 0.0

    phases: dict[str, float] = {}
    curve = []
    for e in events:
        if e.get("kind") != "iteration":
            continue
        for phase, secs in (e.get("phases") or {}).items():
            phases[phase] = phases.get(phase, 0.0) + secs
        curve.append({"iteration": e.get("iteration"),
                      "rank": e.get("rank", 0),
                      "steps_per_sec": e.get("steps_per_sec"),
                      "wall_s": e.get("wall_s")})

    startups = [{"rank": e.get("rank", 0), **e["startup"]}
                for e in events
                if e.get("kind") == "run_start" and e.get("startup")]
    history = [e for e in events if e.get("kind") in _HISTORY_KINDS]
    fleet = [e for e in events if e.get("kind") in _FLEET_KINDS]
    restores = [e for e in events if e.get("kind") == "ckpt_restore"]
    chaos = [{"regime": e.get("regime"), "scheduler": e.get("scheduler"),
              "avg_jct": e.get("avg_jct"),
              "completion": e.get("completion"),
              "degradation": e.get("degradation"),
              "n_drains": e.get("fault_n_drains"),
              "chaos_seed": e.get("chaos_seed")}
             for e in events if e.get("kind") == "env_fault"]
    alarms = {k: sum(1 for e in events if e.get("kind") == k)
              for k in ALARM_KINDS}
    counts: dict[str, int] = {}
    for e in events:
        k = str(e.get("kind"))
        counts[k] = counts.get(k, 0) + 1
    has_spans = any(e.get("kind") in SPAN_KINDS for e in events)
    span_tree = build_span_tree(events) if has_spans else []
    return {"schema_versions": versions, "ranks": ranks,
            "n_events": len(events), "span_s": span_s, "t0_mono": t0,
            "startup": startups,
            "phase_seconds": phases, "steps_curve": curve,
            "history": history, "fleet": fleet,
            "ckpt_restores": restores,
            "chaos": chaos, "alarms": alarms, "kind_counts": counts,
            "span_tree": span_tree,
            "torn_spans": sum(n["open"] for n in span_tree),
            "async_overlap": (async_overlap_summary(events)
                              if has_spans else None)}


# flight-log deadline-outcome codes (flywheel.flightlog schema)
_OUTCOME_NAMES = {0: "no-deadline", 1: "met", 2: "served-late"}


def build_request_report(events: list[dict], req_id: int,
                         flight_dir: "str | None" = None) -> dict:
    """Join one request id across the serve instants, the flight log,
    and the promotion ledger — the single-request timeline.

    Stages come from the batching tier's ``span_point`` instants:
    ``enqueue`` (admission), then exactly one of ``served`` (with the
    per-row queue wait and end-to-end latency the dispatch recorded),
    ``shed`` (admission or in-queue expiry), or ``dispatch_failed``.
    With ``flight_dir`` the id is also looked up in the sealed shards'
    ``req_id`` column (which shard/row logged the decision) and — via
    the row's global position — matched against ledger entries whose
    canary window covered it."""
    req_id = int(req_id)
    stages = []

    def stage(name, e, **extra):
        stages.append(dict({"stage": name, "mono": e.get("mono"),
                            "rank": e.get("rank", 0)}, **extra))

    for e in events:
        if e.get("kind") != "span_point":
            continue
        a = e.get("attrs") or {}
        span = e.get("span")
        if span == "enqueue" and a.get("req_id") == req_id:
            stage("enqueue", e, stall=a.get("stall"))
        elif span == "shed" and a.get("req_id") == req_id:
            stage("shed", e, reason=a.get("reason"))
        elif span in ("served", "dispatch_failed"):
            rids = a.get("req_ids") or []
            if req_id not in rids:
                continue
            if span == "served":
                i = rids.index(req_id)
                waits = a.get("wait_ms") or []
                lats = a.get("lat_ms") or []
                stage("served", e, bucket=a.get("bucket"),
                      batch_rows=len(rids),
                      queue_wait_ms=waits[i] if i < len(waits) else None,
                      latency_ms=lats[i] if i < len(lats) else None)
            else:
                stage("dispatch_failed", e, error=a.get("error"))

    flight = None
    verdicts: list[dict] = []
    if flight_dir:
        import numpy as np

        from ..flywheel.canary import read_ledger
        from ..flywheel.flightlog import read_flight_log
        data = read_flight_log(flight_dir)
        preceding = 0
        for s in data.shards:
            if s.req_id is not None:
                for i in np.flatnonzero(s.req_id == req_id):
                    i = int(i)
                    flight = {"shard_seq": s.seq, "path": s.path,
                              "row": i, "global_row": preceding + i,
                              "outcome": int(s.outcome[i]),
                              "outcome_name": _OUTCOME_NAMES.get(
                                  int(s.outcome[i]), "?")}
            preceding += s.rows
        if flight is not None:
            sealed, tail = read_ledger(flight_dir)
            for entry in sealed + tail:
                rows = entry.get("window_rows")
                if rows is not None and int(rows) > flight["global_row"]:
                    verdicts.append(
                        {"action": entry.get("action"),
                         "verdict": entry.get("verdict"),
                         "candidate": entry.get("candidate"),
                         "window_rows": int(rows),
                         "sealed": entry in sealed})
    return {"req_id": req_id, "stages": stages, "flight": flight,
            "verdicts": verdicts,
            "found": bool(stages or flight is not None)}


def format_request_report(rep: dict) -> str:
    """The human single-request timeline."""
    rid = rep["req_id"]
    lines = [f"request 0x{rid:016x} ({rid}):"]
    if not rep["found"]:
        lines.append("  not found: no serve instant, flight-log row, or "
                     "ledger verdict carries this id")
        return "\n".join(lines)
    t0 = min((s["mono"] for s in rep["stages"]
              if s.get("mono") is not None), default=0.0)
    for s in rep["stages"]:
        t = (s["mono"] - t0) if s.get("mono") is not None else 0.0
        detail = " ".join(
            f"{k}={v}" for k, v in sorted(s.items())
            if k not in ("stage", "mono", "rank") and v is not None)
        lines.append(f"  +{t:9.3f}s  rank {s.get('rank', '?'):>3}  "
                     f"{s['stage']:<16s} {detail}")
    if rep["flight"] is not None:
        f = rep["flight"]
        lines.append(
            f"  logged: shard {f['shard_seq']:06d} row {f['row']} "
            f"(global row {f['global_row']}, outcome "
            f"{f['outcome_name']}) — {f['path']}")
    elif not rep["verdicts"]:
        lines.append("  logged: no flight-log row (shed, failed, "
                     "unsealed tail, or no --flight-log given)")
    for v in rep["verdicts"]:
        seal = "sealed" if v["sealed"] else "unsealed tail"
        lines.append(
            f"  replayed: ledger {v['action']} "
            f"(verdict={v['verdict']}, candidate={v['candidate']}, "
            f"window={v['window_rows']} rows, {seal})")
    if rep["flight"] is not None and not rep["verdicts"]:
        lines.append("  replayed: no canary window covered this row yet")
    return "\n".join(lines)


def _fmt_history_line(e: dict, t0: float) -> str:
    t = e.get("mono", t0) - t0
    rank = e.get("rank", "?")
    detail = {k: v for k, v in e.items()
              if k not in ("v", "kind", "rank", "pid", "seq", "mono",
                           "wall")}
    body = " ".join(f"{k}={v}" for k, v in sorted(detail.items())
                    if v is not None)
    return f"  +{t:9.3f}s  rank {rank:>3}  {e.get('kind'):<22s} {body}"


# the start-up summary's exclusive parts, in the order a process pays them
_STARTUP_PARTS = (("import_s", "import"), ("backend_s", "backend"),
                  ("build_s", "build"), ("trace_lower_s", "trace + lower"),
                  ("compile_s", "compile"), ("cache_load_s", "cache load"),
                  ("run_s", "run"), ("unnamed_s", "(unnamed)"))


def _format_startup(st: dict) -> list[str]:
    """One ``run_start`` event's start-up account as a table."""
    total = st.get("until_s") or 1.0
    c = st.get("counts", {})
    lines = [f"start-up table (rank {st.get('rank', 0)}: process start to "
             f"run start, {st.get('until_s', 0.0):.3f}s; exclusive seconds; "
             f"{c.get('programs', 0)} programs, {c.get('traces', 0)} traces, "
             f"{c.get('backend_compiles', 0)} backend compiles, "
             f"{c.get('cache_hits', 0)} cache hits, "
             f"{c.get('cache_misses', 0)} misses):",
             f"  {'part':<22s} {'seconds':>10s} {'share':>7s}"]
    for key, label in _STARTUP_PARTS:
        secs = st.get(key, 0.0)
        lines.append(f"  {label:<22s} {secs:>10.3f} "
                     f"{100.0 * secs / total:>6.1f}%")
        # under build its phases (exclusive too); under run the loops'
        # own sections (wall seconds, a first step's compiles included)
        inner = {"build_s": "build_by_phase", "run_s": "run_sections"}
        for name, secs in sorted((st.get(inner.get(key)) or {}).items(),
                                 key=lambda kv: -kv[1]):
            lines.append(f"    {name:<20s} {secs:>10.3f}")
    if st.get("programs"):
        lines.append(f"  {'program':<22s} {'trace s':>9s} {'lower s':>9s} "
                     f"{'compile s':>9s} {'load s':>9s}")
        for p in st["programs"]:
            lines.append(
                f"  {p['fun'][:22]:<22s} {p['trace_s']:>9.3f} "
                f"{p['lower_s']:>9.3f} {p['compile_s']:>9.3f} "
                f"{p['cache_load_s']:>9.3f}")
    lines.append("")
    return lines


def format_report(rep: dict) -> str:
    """The human post-mortem. Sections keyed to build_report's dict."""
    lines = [
        f"run post-mortem: {rep['n_events']} events from "
        f"{len(rep['ranks'])} emitter(s) (ranks {rep['ranks']}), "
        f"schema v{rep['schema_versions']}, span {rep['span_s']:.3f}s",
        "",
    ]
    for st in rep.get("startup") or ():
        lines.extend(_format_startup(st))
    if rep["phase_seconds"]:
        total = sum(rep["phase_seconds"].values()) or 1.0
        lines.append("phase-time table (host wall, from iteration spans):")
        lines.append(f"  {'phase':<12s} {'seconds':>10s} {'share':>7s}")
        for phase, secs in sorted(rep["phase_seconds"].items(),
                                  key=lambda kv: -kv[1]):
            lines.append(f"  {phase:<12s} {secs:>10.3f} "
                         f"{100.0 * secs / total:>6.1f}%")
        lines.append("")
    if rep.get("span_tree"):
        lines.append("span tree (flight recorder, self/child time):")
        lines.append(f"  {'span':<28s} {'count':>6s} {'total s':>10s} "
                     f"{'self s':>10s}")
        for n in rep["span_tree"]:
            label = "  " * n["depth"] + n["name"] + \
                (f"  [open x{n['open']}]" if n["open"] else "")
            lines.append(f"  {label:<28s} {n['count']:>6d} "
                         f"{n['total_s']:>10.3f} {n['self_s']:>10.3f}")
        if rep.get("torn_spans"):
            lines.append(f"  ({rep['torn_spans']} torn span(s): begin "
                         f"with no end — writer died mid-span)")
        lines.append("")
    if rep.get("async_overlap"):
        ov = rep["async_overlap"]
        lines.append(
            f"async occupancy (measured from actor/learner spans): "
            f"async_overlap_measured={ov['async_overlap_measured']:.3f} "
            f"(window {ov['window_s']:.3f}s, actor busy "
            f"{ov['actor_busy_s']:.3f}s, learner busy "
            f"{ov['learner_busy_s']:.3f}s, concurrent "
            f"{ov['concurrent_s']:.3f}s, idle {ov['idle_s']:.3f}s)")
        lines.append("")
    if rep.get("skew", {}).get("applied"):
        sk = rep["skew"]
        ranks = ", ".join(
            f"rank {r}: shift {v['shift_s']*1e3:+.3f}ms "
            f"(±{v['residual_s']*1e3:.3f}ms, n={v['n_samples']})"
            for r, v in sk["ranks"].items())
        lines.append(
            f"clock skew: timeline rewritten onto rank "
            f"{sk['reference_rank']}'s monotonic axis — {ranks}; "
            f"max residual {sk['max_residual_s']*1e3:.3f}ms")
        lines.append("")
    if rep["history"]:
        lines.append("restart / rollback / fault history:")
        for e in rep["history"]:
            lines.append(_fmt_history_line(e, rep["t0_mono"]))
        lines.append("")
    if rep["steps_curve"]:
        lines.append("steps/s curve (logged iterations):")
        lines.append(f"  {'iter':>6s} {'rank':>4s} {'steps/s':>12s} "
                     f"{'iter wall s':>12s}")
        for row in rep["steps_curve"]:
            sps = row.get("steps_per_sec")
            wall = row.get("wall_s")
            lines.append(
                f"  {row.get('iteration', '?'):>6} "
                f"{row.get('rank', 0):>4} "
                f"{(f'{sps:.1f}' if sps is not None else '?'):>12s} "
                f"{(f'{wall:.4f}' if wall is not None else '?'):>12s}")
        lines.append("")
    if rep.get("fleet"):
        by_kind = {}
        for e in rep["fleet"]:
            k = str(e.get("kind"))
            by_kind[k] = by_kind.get(k, 0) + 1
        summary = ", ".join(f"{k}={n}" for k, n in sorted(by_kind.items()))
        lines.append(f"flywheel & fleet health ({summary}):")
        for e in rep["fleet"]:
            lines.append(_fmt_history_line(e, rep["t0_mono"]))
        lines.append("")
    if rep.get("chaos"):
        lines.append("chaos story (env_fault events, evaluate --chaos):")
        lines.append(f"  {'regime':<12s} {'scheduler':<10s} "
                     f"{'avg JCT s':>10s} {'done':>6s} {'vs clean':>9s} "
                     f"{'drains':>7s}")
        for c in rep["chaos"]:
            deg = c.get("degradation")
            done = c.get("completion")
            jct = c.get("avg_jct")
            lines.append(
                f"  {str(c.get('regime')):<12s} "
                f"{str(c.get('scheduler')):<10s} "
                f"{(f'{jct:.1f}' if jct is not None else '?'):>10s} "
                f"{(f'{done:.0%}' if done is not None else '?'):>6s} "
                f"{(f'x{deg:.2f}' if deg is not None else '—'):>9s} "
                f"{str(c.get('n_drains', '?')):>7s}")
        lines.append("")
    alarm_total = sum(rep["alarms"].values())
    lines.append(
        "alarms: " + ", ".join(f"{k}={n}"
                               for k, n in sorted(rep["alarms"].items()))
        + ("" if alarm_total else "  (clean)"))
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="rlgpuschedule_tpu.obs.report",
        description="Merge per-rank event streams into one timeline and "
                    "print a run post-mortem.")
    p.add_argument("obs_dir", help="directory holding events.*.jsonl "
                                   "streams (--obs-dir of the run)")
    p.add_argument("--json", action="store_true",
                   help="print the structured report as JSON instead of "
                        "the human tables")
    p.add_argument("--out", default=None,
                   help="also write the merged ordered timeline to this "
                        "JSONL file")
    p.add_argument("--trace-out", default=None,
                   help="write the timeline as Chrome-trace JSON "
                        "(open in Perfetto / chrome://tracing)")
    p.add_argument("--no-skew-correct", action="store_true",
                   help="keep each rank's raw monotonic axis instead of "
                        "rewriting onto the learned corrected axis")
    p.add_argument("--strict-alarms", action="store_true",
                   help="exit 1 if any post-warmup alarm event "
                        f"({'/'.join(ALARM_KINDS)}) fired")
    p.add_argument("--request", default=None, metavar="ID",
                   help="print the single-request timeline for this "
                        "64-bit request id (decimal or 0x-hex) instead "
                        "of the run post-mortem; exit 1 if the id "
                        "appears nowhere")
    p.add_argument("--flight-log", default=None, metavar="DIR",
                   help="with --request: also join the id against this "
                        "flight-log directory's shards and promotion "
                        "ledger")
    args = p.parse_args(argv)
    try:
        events = merge_dir(args.obs_dir)
    except FileNotFoundError as e:
        print(str(e), file=sys.stderr)
        return 1
    if not events:
        print(f"event streams under {args.obs_dir} hold no decodable "
              f"events", file=sys.stderr)
        return 1
    skew_info: dict = {"applied": False}
    if not args.no_skew_correct:
        events, skew_info = correct_events(events)
    if args.request is not None:
        try:
            req_id = int(args.request, 0)
        except ValueError:
            print(f"--request: {args.request!r} is not an integer id",
                  file=sys.stderr)
            return 2
        req = build_request_report(events, req_id,
                                   flight_dir=args.flight_log)
        if args.json:
            print(json.dumps(req, sort_keys=True))
        else:
            print(format_request_report(req))
        return 0 if req["found"] else 1
    if args.out:
        with open(args.out, "w") as f:
            for e in events:
                f.write(json.dumps(e, sort_keys=True) + "\n")
    if args.trace_out:
        with open(args.trace_out, "w") as f:
            json.dump(to_chrome_trace(events), f)
    rep = build_report(events)
    rep["skew"] = skew_info
    if args.json:
        print(json.dumps(rep, sort_keys=True))
    else:
        print(format_report(rep))
    if args.strict_alarms and sum(rep["alarms"].values()) > 0:
        print(f"strict-alarms: {rep['alarms']} alarm event(s) in the "
              f"timeline", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
