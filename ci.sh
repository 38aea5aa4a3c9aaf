#!/usr/bin/env bash
# CI pipeline: lint stage (PR 3), the observability smoke stage
# (ISSUE 5: a telemetry-instrumented 3-iteration run must produce a
# reportable merged timeline with zero post-warmup alarms), then the
# tier-1 pytest gate.
#
# Stage 1 — lint (fast, no JAX import for jsan's AST pass):
#   1a. jsan: the repo's JAX-pitfall + concurrency static analyzer.
#       Scope is the package + the top-level entry scripts. tests/ is
#       NOT scanned: single-shot jit(lambda) in a test body is benign
#       (each test compiles once by design) and tests/fixtures/ holds
#       jsan's own deliberately-bad corpus. Baseline:
#       jsan_baseline.json (EMPTY since PR 15), run with --fail-stale
#       so the baseline can only shrink. Both invocations share a
#       --cache dir (PR 18) keyed on (file sha1, analyzer-source sha1):
#       the SARIF pass replays the text pass's per-file results instead
#       of re-analyzing, and repeat CI runs skip unchanged files
#       entirely (cross-file rules always re-run). A second jsan
#       invocation emits SARIF and sanity-checks its shape, including
#       the PR-18 column regions — the code-scanning upload must never
#       receive a malformed document.
#   1b. ruff + mypy at the pyproject.toml config, pinned there
#       (ruff==0.6.9, mypy==1.11.2). Both gate on availability: the
#       hermetic CI image does not ship them, and the lint stage must
#       not mutate the environment by installing things — when absent
#       they are SKIPPED LOUDLY, not failed. When PRESENT, the version
#       must match the pin exactly: a drifted linter silently applies
#       different rules, which is worse than no linter.
#
# Stage 2 — the tier-1 gate (ROADMAP.md), split in two: the main pass
#   excludes the multihost_spawn subset, which then runs SERIALLY after
#   it. The spawn tests fork real jax.distributed gangs whose gloo
#   collective rendezvous (~30s window) races per-rank XLA compile —
#   on a small rig, running them next to the rest of the suite's CPU
#   load is the reproducible way to flake them. The ROADMAP one-liner
#   (everything in one pass) stays the driver's acceptance command;
#   this split is strictly more conservative.
set -euo pipefail
cd "$(dirname "$0")"

echo "=== lint 1/3: jsan (python -m rlgpuschedule_tpu.analysis) ==="
JSAN_CACHE="${JSAN_CACHE:-.jsan_cache}"
python -m rlgpuschedule_tpu.analysis \
    rlgpuschedule_tpu chip_smoke.py __graft_entry__.py \
    --baseline jsan_baseline.json --fail-stale --cache "$JSAN_CACHE"

echo "=== lint 1/3b: jsan SARIF gate (warm --cache replay) ==="
JSAN_SARIF=$(mktemp /tmp/ci_jsan.XXXXXX.sarif)
python -m rlgpuschedule_tpu.analysis \
    rlgpuschedule_tpu chip_smoke.py __graft_entry__.py \
    --baseline jsan_baseline.json --format sarif \
    --cache "$JSAN_CACHE" > "$JSAN_SARIF"
python - "$JSAN_SARIF" <<'PY'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["version"] == "2.1.0", doc.get("version")
assert "sarif-schema-2.1.0" in doc["$schema"]
run, = doc["runs"]
assert run["tool"]["driver"]["name"] == "jsan"
rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
for res in run["results"]:
    assert res["ruleId"] in rule_ids, res["ruleId"]
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"]
    region = loc["region"]
    assert region["startLine"] >= 1 and region["startColumn"] >= 1
    assert region["endLine"] >= region["startLine"]
    assert region["endColumn"] > region["startColumn"]  # exclusive end
print(f"sarif ok: {len(run['results'])} result(s), "
      f"{len(rule_ids)} rules declared, column regions present")
PY
rm -f "$JSAN_SARIF"

echo "=== lint 2/3: ruff ==="
if command -v ruff >/dev/null 2>&1; then
    want=$(sed -n 's/^#   ruff==//p' pyproject.toml)
    have=$(ruff --version | awk '{print $2}')
    if [ "$have" != "$want" ]; then
        echo "FAIL: ruff $have installed but pyproject.toml pins ruff==$want" >&2
        exit 1
    fi
    ruff check rlgpuschedule_tpu tests chip_smoke.py
else
    echo "SKIP: ruff not installed (pinned ruff==0.6.9 in pyproject.toml)"
fi

echo "=== lint 3/3: mypy ==="
if command -v mypy >/dev/null 2>&1; then
    want=$(sed -n 's/^#   mypy==//p' pyproject.toml)
    have=$(mypy --version | awk '{print $2}')
    if [ "$have" != "$want" ]; then
        echo "FAIL: mypy $have installed but pyproject.toml pins mypy==$want" >&2
        exit 1
    fi
    mypy
else
    echo "SKIP: mypy not installed (pinned mypy==1.11.2 in pyproject.toml)"
fi

echo "=== smoke: observability (3-iter CPU run + merged-timeline report) ==="
# A short geometry-stable training run with the full telemetry layer on
# must (a) produce a timeline the report CLI accepts and (b) fire ZERO
# recompile/transfer alarms after warmup — --strict-alarms asserts both
# in one exit code (ISSUE 5 acceptance).
OBS_DIR=$(mktemp -d /tmp/ci_obs.XXXXXX)
ASYNC_OBS_DIR=$(mktemp -d /tmp/ci_async_obs.XXXXXX)
VTRACE_OBS_DIR=$(mktemp -d /tmp/ci_vtrace_obs.XXXXXX)
SERVE_OBS_DIR=$(mktemp -d /tmp/ci_serve_obs.XXXXXX)
SOAK_OBS_DIR=$(mktemp -d /tmp/ci_soak_obs.XXXXXX)
CHAOS_SOAK_OBS_DIR=$(mktemp -d /tmp/ci_chaos_soak_obs.XXXXXX)
CHAOS_FLOG_DIR=$(mktemp -d /tmp/ci_chaos_flog.XXXXXX)
CHAOS_JSON=$(mktemp /tmp/ci_chaos.XXXXXX.json)
SERVE_JSON=$(mktemp /tmp/ci_serve.XXXXXX.json)
SOAK_JSON=$(mktemp /tmp/ci_soak.XXXXXX.json)
CHAOS_SOAK_JSON=$(mktemp /tmp/ci_chaos_soak.XXXXXX.json)
TRACE_JSON=$(mktemp /tmp/ci_trace.XXXXXX.json)
trap 'rm -rf "$OBS_DIR" "$ASYNC_OBS_DIR" "$VTRACE_OBS_DIR" \
    "$SERVE_OBS_DIR" "$SOAK_OBS_DIR" "$CHAOS_SOAK_OBS_DIR" \
    "$CHAOS_FLOG_DIR" \
    "$CHAOS_JSON" "$SERVE_JSON" "$SOAK_JSON" "$CHAOS_SOAK_JSON" \
    "$TRACE_JSON"' EXIT
# --trace-spans rides along (ISSUE 11): the flight recorder must not
# disturb the strict-alarms gate, and the exported Chrome trace must be
# Perfetto-valid (validated per layer below)
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.train --config ppo-mlp-synth64 \
    --iterations 3 --n-envs 4 --n-nodes 2 --gpus-per-node 4 \
    --window-jobs 16 --horizon 64 --queue-len 4 --n-steps 8 \
    --n-epochs 1 --n-minibatches 2 --log-every 1 \
    --obs-dir "$OBS_DIR" --alarms --trace-spans > /dev/null
# Perfetto-validity gate, shared by the sync/async/serve layers: the
# Chrome trace must load as JSON, every (pid,tid) track must carry
# strictly paired B/E events, at least one span must nest (depth >= 2),
# and a clean run must contain no torn spans.
validate_trace() {  # $1 = trace json path, $2 = layer label
python - "$1" "$2" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))   # valid JSON or this line throws
depth, max_depth = {}, 0
for e in doc["traceEvents"]:
    if e["ph"] not in ("B", "E"):
        continue
    key = (e["pid"], e["tid"])
    depth[key] = depth.get(key, 0) + (1 if e["ph"] == "B" else -1)
    assert depth[key] >= 0, f"unpaired E on {key}"
    max_depth = max(max_depth, depth[key])
assert not any(depth.values()), f"unpaired B: {depth}"
assert not any(e.get("args", {}).get("torn")
               for e in doc["traceEvents"]), "torn spans in a clean run"
assert max_depth >= 2, f"expected nested spans, max depth {max_depth}"
print(f"trace smoke ok ({sys.argv[2]}): "
      f"{len(doc['traceEvents'])} events, max span depth {max_depth}")
EOF
}
timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.obs.report "$OBS_DIR" --strict-alarms \
    --trace-out "$TRACE_JSON" > /dev/null
validate_trace "$TRACE_JSON" sync

echo "=== smoke: async actor-learner (3-iter overlapped run, 2 CPU devices) ==="
# ISSUE 9 acceptance: a telemetry-instrumented train --async run on a
# 2-virtual-device CPU rig must (a) pass the same strict-alarms gate as
# the sync smoke (zero post-warmup recompile/transfer alarms — the
# engine AOT-compiles both programs up front), and (b) leave a run_end
# event carrying nonzero actor AND learner phase seconds plus the
# engine's overlap counter — proof the split actually ran both loops.
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    python -m rlgpuschedule_tpu.train --config ppo-mlp-synth64 \
    --async --staleness-bound 1 \
    --iterations 3 --n-envs 4 --n-nodes 2 --gpus-per-node 4 \
    --window-jobs 16 --horizon 64 --queue-len 4 --n-steps 8 \
    --n-epochs 1 --n-minibatches 2 --log-every 1 \
    --obs-dir "$ASYNC_OBS_DIR" --alarms --trace-spans > /dev/null
# ISSUE 11 acceptance: the traced async run exports a Perfetto-valid
# trace AND the report upgrades the overlap headline from the phase-time
# projection to measured occupancy (async_overlap_measured)
timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.obs.report "$ASYNC_OBS_DIR" --strict-alarms \
    --trace-out "$TRACE_JSON" | tee /tmp/_async_report.log
grep -q "async_overlap_measured" /tmp/_async_report.log
validate_trace "$TRACE_JSON" async
python - "$ASYNC_OBS_DIR" <<'EOF'
import sys
from rlgpuschedule_tpu.obs import merge_dir
from rlgpuschedule_tpu.obs.trace import SPAN_BEGIN, async_overlap_summary
events = merge_dir(sys.argv[1])
end = next(e for e in events if e["kind"] == "run_end")
ph = end["phase_seconds"]
assert ph.get("actor", 0) > 0 and ph.get("learner", 0) > 0, ph
assert "async_overlap_s" in end and "async_staleness_max" in end, end
assert not [e for e in events if e["kind"] == "recompile"], "recompiles"
# the actor thread and the learner (caller) thread must land on
# DISTINCT tracks — that is what makes the occupancy math meaningful
begins = [e for e in events if e["kind"] == SPAN_BEGIN]
tids = {e["tid"] for e in begins if e["span"] in ("actor", "learner")}
assert len(tids) == 2, f"actor/learner share a track: {tids}"
occ = async_overlap_summary(events)
assert occ is not None, "no actor/learner spans in the traced async run"
measured = occ["async_overlap_measured"]
assert 0 < measured <= 1, occ
print("async smoke ok:", {"actor_s": round(ph["actor"], 3),
                          "learner_s": round(ph["learner"], 3),
                          "overlap_s": round(end["async_overlap_s"], 3),
                          "overlap_measured": round(measured, 3),
                          "staleness_max": end["async_staleness_max"]})
EOF

echo "=== smoke: deep-staleness V-trace (bound=4 overlapped run, 2 CPU devices) ==="
# ISSUE 12 acceptance: the off-policy-corrected engine must run the
# trajectory queue DEEP (staleness bound 4) under the same strict-alarms
# gate as the bound-1 smoke, and the run_end event must carry the
# importance-ratio gauge pair with the staleness counter above 1 — proof
# the V-trace ratio recompute executed against genuinely stale batches
# (the gauges feed from logged metrics, hence --log-every 1).
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    python -m rlgpuschedule_tpu.train --config ppo-mlp-synth64 \
    --async --staleness-bound 4 --correction vtrace \
    --iterations 6 --n-envs 4 --n-nodes 2 --gpus-per-node 4 \
    --window-jobs 16 --horizon 64 --queue-len 4 --n-steps 8 \
    --n-epochs 1 --n-minibatches 2 --log-every 1 \
    --obs-dir "$VTRACE_OBS_DIR" --alarms > /dev/null
timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.obs.report "$VTRACE_OBS_DIR" \
    --strict-alarms > /dev/null
python - "$VTRACE_OBS_DIR" <<'EOF'
import math, sys
from rlgpuschedule_tpu.obs import merge_dir
events = merge_dir(sys.argv[1])
end = next(e for e in events if e["kind"] == "run_end")
for k in ("async_importance_ratio_mean", "async_importance_ratio_max"):
    assert k in end and math.isfinite(end[k]) and end[k] > 0, \
        (k, end.get(k))
assert end["async_staleness_max"] >= 1, end["async_staleness_max"]
assert not [e for e in events if e["kind"] == "recompile"], "recompiles"
print("vtrace smoke ok:", {
    "rho_mean": round(end["async_importance_ratio_mean"], 4),
    "rho_max": round(end["async_importance_ratio_max"], 4),
    "staleness_max": end["async_staleness_max"]})
EOF

echo "=== smoke: chaos matrix (2 regimes x policy+SJF, CPU) ==="
# ISSUE 6 acceptance: a tiny evaluate --chaos matrix must exit 0, keep
# the no-jobs-lost conservation contract, and carry per-regime
# degradation in its JSON (the satellite's chaos smoke stage)
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.evaluate --config ppo-mlp-synth64 \
    --chaos --chaos-regimes sporadic --chaos-baselines sjf \
    --n-envs 2 --n-nodes 2 --gpus-per-node 4 --window-jobs 16 \
    --queue-len 4 --horizon 256 --max-steps 256 > "$CHAOS_JSON"
python - "$CHAOS_JSON" <<'EOF'
import json, sys
rep = json.load(open(sys.argv[1]))
assert rep["jobs_lost"] == 0, f"jobs lost under faults: {rep['jobs_lost']}"
assert set(rep["regimes"]) == {"none", "sporadic"}, rep["regimes"].keys()
for regime, rows in rep["regimes"].items():
    for sched, row in rows.items():
        assert row["degradation"] is not None, (regime, sched)
assert rep["repro"]["chaos_seed"] == 0
print("chaos smoke ok:", {r: round(rows["policy"]["degradation"], 3)
                          for r, rows in rep["regimes"].items()})
EOF

echo "=== smoke: generalization matrix (train --domains -> 2x2 cross table, CPU) ==="
# ISSUE 14 acceptance: a tiny train --domains run plus a clean twin feed
# evaluate --matrix, which must produce the train-regime x eval-regime
# cross table (mixed + clean + SJF rows, none + overload columns) with
# no jobs lost against the DRAWN capacities, degradation in every cell,
# and — under --alarms — zero post-warmup recompiles (one compiled step
# serves the whole domain distribution; strict-alarms is the gate).
MATRIX_OBS_DIR=$(mktemp -d /tmp/ci_matrix_obs.XXXXXX)
MATRIX_CKPT_DIR=$(mktemp -d /tmp/ci_matrix_ckpt.XXXXXX)
MATRIX_CLEAN_DIR=$(mktemp -d /tmp/ci_matrix_clean.XXXXXX)
MATRIX_JSON=$(mktemp /tmp/ci_matrix.XXXXXX.json)
trap 'rm -rf "$OBS_DIR" "$ASYNC_OBS_DIR" "$VTRACE_OBS_DIR" \
    "$SERVE_OBS_DIR" "$SOAK_OBS_DIR" "$CHAOS_SOAK_OBS_DIR" \
    "$CHAOS_FLOG_DIR" \
    "$CHAOS_JSON" "$SERVE_JSON" "$SOAK_JSON" "$CHAOS_SOAK_JSON" \
    "$TRACE_JSON" \
    "$MATRIX_OBS_DIR" "$MATRIX_CKPT_DIR" "$MATRIX_CLEAN_DIR" \
    "$MATRIX_JSON"' EXIT
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.train --config ppo-mlp-synth64 \
    --domains mixed \
    --iterations 2 --n-envs 2 --n-nodes 2 --gpus-per-node 4 \
    --window-jobs 16 --horizon 64 --queue-len 4 --n-steps 8 \
    --n-epochs 1 --n-minibatches 2 --log-every 1 \
    --ckpt-dir "$MATRIX_CKPT_DIR" --ckpt-every 1 > /dev/null
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.train --config ppo-mlp-synth64 \
    --iterations 2 --n-envs 2 --n-nodes 2 --gpus-per-node 4 \
    --window-jobs 16 --horizon 64 --queue-len 4 --n-steps 8 \
    --n-epochs 1 --n-minibatches 2 --log-every 1 \
    --ckpt-dir "$MATRIX_CLEAN_DIR" --ckpt-every 1 > /dev/null
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.evaluate --config ppo-mlp-synth64 \
    --domains mixed --ckpt-dir "$MATRIX_CKPT_DIR" \
    --matrix --matrix-regimes overload --matrix-baselines sjf \
    --matrix-ckpt clean="$MATRIX_CLEAN_DIR" \
    --n-envs 2 --n-nodes 2 --gpus-per-node 4 --window-jobs 16 \
    --queue-len 4 --horizon 256 --max-steps 256 \
    --obs-dir "$MATRIX_OBS_DIR" --alarms > "$MATRIX_JSON"
timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.obs.report "$MATRIX_OBS_DIR" \
    --strict-alarms > /dev/null
python - "$MATRIX_JSON" "$MATRIX_OBS_DIR" <<'EOF'
import json, sys
rep = json.load(open(sys.argv[1]))
assert rep["jobs_lost"] == 0, f"jobs lost under domains: {rep['jobs_lost']}"
assert set(rep["cells"]) == {"none", "overload"}, rep["cells"].keys()
for regime, rows in rep["cells"].items():
    assert set(rows) == {"mixed", "clean", "sjf"}, (regime, rows.keys())
    for sched, row in rows.items():
        assert row["degradation"] is not None, (regime, sched)
assert rep["domain_stats"]["overload"]["mean_load"] > 1.5
assert rep["repro"]["matrix_seed"] == 0
assert rep["repro"]["matrix_ckpts"], rep["repro"]
from rlgpuschedule_tpu.obs import read_events
events = read_events(sys.argv[2] + "/events.matrix.jsonl")
cells = [e for e in events if e["kind"] == "domain_cell"]
assert len(cells) == 6, f"expected 2 regimes x 3 rows, got {len(cells)}"
prom = open(sys.argv[2] + "/metrics.prom").read()
assert "matrix_overload_mixed_degradation" in prom
print("matrix smoke ok:", {f"{r}/{s}": round(row["degradation"], 3)
                           for r, rows in rep["cells"].items()
                           for s, row in rows.items()})
EOF

echo "=== smoke: serving (bench + fleet replay, CPU) ==="
# ISSUE 7 acceptance: a short serve --bench must report p50/p99 decision
# latency and nonzero decisions/s with ZERO post-warmup recompiles
# across >= 3 distinct request sizes in one bucket, the fleet replay
# must complete, and the live scrape endpoint must answer with a
# well-formed Prometheus exposition (the CLI self-scrapes and records
# the verdict in its JSON).
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.serve --config ppo-mlp-synth64 \
    --bench --fleet 2 --bucket 8 --rounds 9 --pool-steps 2 \
    --n-envs 2 --n-nodes 2 --gpus-per-node 4 --window-jobs 16 \
    --queue-len 4 --horizon 64 --max-steps 96 \
    --obs-dir "$SERVE_OBS_DIR" --trace-spans \
    --metrics-port 0 > "$SERVE_JSON"
# the request lifecycle must land on the flight recorder too:
# serve_batch > arena_seal / (engine) pad > dispatch > scatter
timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.obs.report "$SERVE_OBS_DIR" \
    --trace-out "$TRACE_JSON" > /dev/null
validate_trace "$TRACE_JSON" serve
python - "$SERVE_OBS_DIR" <<'EOF'
import sys
from rlgpuschedule_tpu.obs import merge_dir
from rlgpuschedule_tpu.obs.trace import SPAN_BEGIN
names = {e["span"] for e in merge_dir(sys.argv[1])
         if e["kind"] == SPAN_BEGIN}
need = {"serve_batch", "arena_seal", "pad", "dispatch", "scatter"}
assert need <= names, f"missing serve spans: {sorted(need - names)}"
EOF
python - "$SERVE_JSON" <<'EOF'
import json, sys
rep = json.load(open(sys.argv[1]))
b = rep["bench"]
assert b["post_warmup_recompiles"] == 0, b
assert b["decisions_per_s"] > 0 and b["latency_p50_ms"] > 0, b
assert len(set(b["request_sizes"])) >= 3 and b["buckets"] == [8], b
fl = rep["fleet"]
assert fl["n_clusters"] == 2 and fl["decisions"] > 0, fl
assert fl["completion"] > 0, fl
sc = rep["scrape"]
assert sc["well_formed"] and sc["status"] == 200, sc
assert sc["metric_lines"] > 0, sc
assert rep["repro"]["config"] == "ppo-mlp-synth64"
print("serve smoke ok:", {"p50_ms": round(b["latency_p50_ms"], 3),
                          "decisions_per_s": round(b["decisions_per_s"]),
                          "fleet_mean_jct": round(fl["mean_jct"], 1)})
EOF

echo "=== smoke: soak-lite (2 routed engines, deadlines + autoscale, 2 CPU devices) ==="
# ISSUE 13 acceptance: a short multi-engine soak — 2 mesh-resolved
# engines, per-request deadlines (shedding armed), adaptive batching,
# live autoscale advisor — must hold a bounded first-half vs
# second-half p99 drift, keep ZERO post-warmup recompiles PER ENGINE,
# export the shed/autoscale/per-engine series on the scrape surface,
# produce a Perfetto-valid trace with zero torn spans (per-engine
# lanes included), and pass the same strict-alarms report gate as
# every other layer.
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    python -m rlgpuschedule_tpu.serve --config ppo-mlp-synth64 \
    --engines 2 --soak 6 --rate 150 --deadline-ms 250 \
    --adaptive-wait --autoscale --bucket 8 --pool-steps 2 \
    --n-envs 2 --n-nodes 2 --gpus-per-node 4 --window-jobs 16 \
    --queue-len 4 --horizon 64 \
    --obs-dir "$SOAK_OBS_DIR" --trace-spans \
    --metrics-port 0 > "$SOAK_JSON"
timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.obs.report "$SOAK_OBS_DIR" \
    --strict-alarms --trace-out "$TRACE_JSON" > /dev/null
validate_trace "$TRACE_JSON" soak-lite
python - "$SOAK_JSON" "$SOAK_OBS_DIR" <<'EOF'
import json, sys
rep = json.load(open(sys.argv[1]))
s = rep["soak"]
assert s["requests"] > 0 and s["served"] > 0, s
# the steady-state contract, per engine — the fleet aggregate can
# hide a single recompiling engine behind a quiet sibling
assert s["per_engine_recompiles"] == [0, 0], s["per_engine_recompiles"]
assert s["post_warmup_recompiles"] == 0, s
assert sum(s["per_engine_rows"]) == s["served"], s
drift = s["p99_drift"]
assert drift is not None and drift < 3.0, f"p99 drift {drift}"
assert 1 <= s["engines_active"] <= 2, s
assert s["serialized_dispatch_cpu"] is True   # honesty bit on this rig
sc = rep["scrape"]
assert sc["well_formed"] and sc["status"] == 200, sc
prom = open(sys.argv[2] + "/metrics.prom").read()
# Bare-name presence checks (serve_shed_total, serve_engines_active,
# serve_autoscale_*) moved to jsan's contract-drift rule, which keeps
# registrations and consumers in lockstep statically. Only the
# per-engine LABEL fanout stays a runtime grep — labels are runtime
# data the static rule cannot see.
for series in ('serve_engine_rows_total{engine="0"}',
               'serve_engine_rows_total{engine="1"}',
               'serve_recompile_alarms_total{engine="0"}',
               'serve_recompile_alarms_total{engine="1"}'):
    assert series in prom, f"missing scrape series: {series}"
print("soak-lite smoke ok:", {
    "requests": s["requests"], "shed": s["shed"],
    "p99_drift": round(drift, 3),
    "engines_active": s["engines_active"],
    "autoscale_resizes": s["autoscale_resizes"],
    "per_engine_rows": s["per_engine_rows"]})
EOF

echo "=== smoke: chaos-soak (engine faults mid-run, HTTP front door, 2 CPU devices) ==="
# ISSUE 16 acceptance: the same routed soak with a seeded fault
# injector killing engine 0 mid-run (two consecutive raises -> eject,
# backoff, blessed re-warm, readmit) and the HTTP front door wrapped
# around the server. The run must hold EXACT conservation
# (submitted == served + shed + failed with failed == 0 — the retry
# hedge absorbs every injected fault), count sheds exactly once
# (registry counter == shed futures observed), keep zero post-warmup
# recompiles per engine, bound the p99 drift, land the full
# eject/readmit/retry lifecycle on the event bus, and prove the drain
# contract on the wire (late submit -> typed refusal, connect refused).
# ISSUE 20 rides along: request-id conservation BY IDENTITY from the
# merged instant stream (every submitted id resolves exactly once as
# served | shed | dispatch_failed), an engine-health slo_burn_alert
# during the fault window with slo_burn_clear + budget recovery after,
# and a single-request timeline reconstruction (report --request) for
# a live sampled id joined against the flight log.
# NOTE: no --autoscale — the chaos soak does not drive the advisor
# loop, and the CLI refuses the combination outright.
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    python -m rlgpuschedule_tpu.serve --config ppo-mlp-synth64 \
    --engines 2 --soak 6 --rate 150 --deadline-ms 250 \
    --adaptive-wait --bucket 8 --pool-steps 2 \
    --n-envs 2 --n-nodes 2 --gpus-per-node 4 --window-jobs 16 \
    --queue-len 4 --horizon 64 \
    --chaos-faults "engine-raise@40:engine=0,engine-raise@40:engine=0" \
    --frontend-port 0 \
    --flight-log "$CHAOS_FLOG_DIR" --flight-capacity 64 \
    --obs-dir "$CHAOS_SOAK_OBS_DIR" --trace-spans \
    --metrics-port 0 > "$CHAOS_SOAK_JSON"
timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.obs.report "$CHAOS_SOAK_OBS_DIR" \
    --strict-alarms --trace-out "$TRACE_JSON" > /dev/null
validate_trace "$TRACE_JSON" chaos-soak
python - "$CHAOS_SOAK_JSON" "$CHAOS_SOAK_OBS_DIR" "$CHAOS_FLOG_DIR" <<'EOF'
import json, sys
from rlgpuschedule_tpu.obs import merge_dir
rep = json.load(open(sys.argv[1]))
s = rep["soak"]
# exact conservation: every submitted request resolved or shed, none
# failed (the retry-once hedge absorbed both injected engine faults),
# and the shed counter agrees with the futures actually observed
assert s["conservation_ok"], s
assert s["requests"] == s["served"] + s["shed"], s
assert s["failed"] == 0, s["failure_kinds"]
assert s["registry_shed_total"] == s["shed"], s
assert s["faults_fired"] == 2, s["faults_fired"]
fs = s["fault_stats"]
assert fs["failures"] >= 2, fs
assert fs["ejections"] >= 1, fs
assert fs["readmissions"] >= 1, fs         # backoff elapsed in-run
assert fs["retry_hedges"] >= 2, fs         # every fault hedged away
assert s["per_engine_recompiles"] == [0, 0], s["per_engine_recompiles"]
assert s["post_warmup_recompiles"] == 0, s
drift = s["p99_drift"]
assert drift is None or drift < 3.0, f"p99 drift {drift}"
assert s["shed_rate"] <= 0.5, s["shed_rate"]
# RSS/heap-drift gate (ISSUE 19 satellite): a faulted soak must not
# leak — eject/re-warm/readmit cycles and the retry hedge all recycle
# buffers, so resident set growth over the run stays a few percent
# (None on /proc-less hosts, where the gate degrades to a no-op)
g = s["rss_growth_frac"]
assert g is None or g < 0.15, (
    f"RSS grew {g:.1%} over the chaos soak "
    f"({s['rss_start_bytes']} -> {s['rss_end_bytes']} bytes)")
# the fault lifecycle must be a readable story on the event bus
kinds = {e["kind"] for e in merge_dir(sys.argv[2])}
for k in ("serve_fault", "engine_eject", "engine_readmit",
          "serve_retry"):
    assert k in kinds, f"missing bus event {k!r}: {sorted(kinds)}"
# wire-level drain contract, proven against the live front door
fe = rep["frontend"]
assert fe["decide_status"] == 200 and fe["decide_has_action"], fe
assert fe["drained"] and fe["late_submit"] == "server-closed", fe
assert fe["post_drain_connect"] == "refused", fe
prom = open(sys.argv[2] + "/metrics.prom").read()
# serve_retry_hedges_total / serve_frontend_requests_total presence is
# enforced statically by jsan's contract-drift rule; only the labeled
# per-engine ejection series needs a runtime grep.
assert 'serve_engine_ejections_total{engine="0"}' in prom, \
    "missing scrape series: serve_engine_ejections_total"
for name in ("serve_queue_wait_seconds_bucket", "slo_burn_rate",
             "slo_error_budget_remaining", "slo_burn_alerts_total"):
    assert name in prom, f"missing scrape series: {name}"

# ---- ISSUE 20: request-id conservation BY IDENTITY -----------------
# the count invariant above cannot see a dropped-and-double-served
# pair; ids can. submitted = enqueued + admission-shed (admission
# sheds never reach the queue, so never emit enqueue); resolved =
# served + shed (any reason) + dispatch_failed — exactly once each.
events = merge_dir(sys.argv[2])
pts = [e for e in events if e.get("kind") == "span_point"]
enq = [e["attrs"]["req_id"] for e in pts if e.get("span") == "enqueue"]
served_ids = [r for e in pts if e.get("span") == "served"
              for r in e["attrs"]["req_ids"]]
shed_pts = [(e["attrs"]["req_id"], e["attrs"]["reason"])
            for e in pts if e.get("span") == "shed"]
failed_ids = [r for e in pts if e.get("span") == "dispatch_failed"
              for r in e["attrs"]["req_ids"]]
submitted = enq + [r for r, why in shed_pts if why == "admission"]
resolved = served_ids + failed_ids + [r for r, _ in shed_pts]
assert len(submitted) == len(set(submitted)), "duplicate submit ids"
assert sorted(resolved) == sorted(submitted), (
    f"request-id conservation violated: {len(submitted)} submitted, "
    f"{len(resolved)} resolved, "
    f"symmetric diff {len(set(submitted) ^ set(resolved))}")
# the soak's own futures are a subset (the frontend selfcheck adds a
# couple of front-door requests after the pacing loop)
assert len(submitted) >= s["requests"], (len(submitted), s["requests"])
assert all(i > 0 for i in submitted), "unassigned (0) id leaked"

# ---- ISSUE 20: burn alert during the fault window, recovery after --
faults = [e for e in events if e["kind"] == "serve_fault"]
eh_alerts = [e for e in events if e["kind"] == "slo_burn_alert"
             and e["slo"] == "engine-health"]
eh_clears = [e for e in events if e["kind"] == "slo_burn_clear"
             and e["slo"] == "engine-health"]
assert eh_alerts, "no engine-health slo_burn_alert under injected faults"
assert faults and eh_alerts[0]["mono"] >= faults[0]["mono"], \
    "burn alert predates the first injected fault"
assert eh_alerts[0]["mono"] <= faults[-1]["mono"] + 2.0, \
    "burn alert fired long after the fault window (stale scrape?)"
assert eh_alerts[0]["burns"] and all(
    b >= 1.0 for b in eh_alerts[0]["burns"].values()), eh_alerts[0]
assert eh_clears and eh_clears[-1]["mono"] > eh_alerts[0]["mono"], \
    "burn alert never cleared after the bleeding stopped"
slo = s["slo"]["engine-health"]
assert not slo["alerting"] and slo["alerts_total"] >= 1, slo
assert slo["budget_remaining"] > 0.5, (
    f"engine-health budget did not recover: {slo}")

# ---- ISSUE 20: single-request timeline reconstruction --------------
# a live served id must reconstruct end to end: stages + the flight-log
# shard/row it landed in (report exits 1 if the id appears nowhere)
import subprocess
rid = served_ids[len(served_ids) // 2]
r = subprocess.run(
    [sys.executable, "-m", "rlgpuschedule_tpu.obs.report", sys.argv[2],
     "--request", f"0x{rid:x}", "--flight-log", sys.argv[3]],
    capture_output=True, text=True, timeout=60)
assert r.returncode == 0, (rid, r.stdout, r.stderr)
assert "logged:" in r.stdout, r.stdout
print("chaos-soak smoke ok:", {
    "requests": s["requests"], "shed": s["shed"],
    "faults_fired": s["faults_fired"],
    "ejections": fs["ejections"],
    "readmissions": fs["readmissions"],
    "retry_hedges": fs["retry_hedges"],
    "rss_growth": (None if g is None else round(g, 4)),
    "frontend": fe["post_drain_connect"],
    "ids_conserved": len(submitted),
    "burn_alerts": len(eh_alerts),
    "budget_recovered": round(slo["budget_remaining"], 3),
    "traced_request": f"0x{rid:x}"})
EOF

echo "=== smoke: sharding (rule-mesh train + PBT-on-mesh, 2 CPU devices) ==="
# ISSUE 10 acceptance: a rule-sharded --mesh auto run and a PBT run
# whose population rides the unified mesh's pop axis must both pass the
# strict-alarms gate (zero post-warmup recompiles — the compile-once
# contract of the rule-resolved in/out_shardings), and the train
# summary must carry the mesh shape + rule-table hash provenance.
MESH_OBS_DIR=$(mktemp -d /tmp/ci_mesh_obs.XXXXXX)
PBT_OBS_DIR=$(mktemp -d /tmp/ci_pbt_obs.XXXXXX)
MESH_JSON=$(mktemp /tmp/ci_mesh.XXXXXX.json)
PBT_JSON=$(mktemp /tmp/ci_pbt.XXXXXX.json)
trap 'rm -rf "$OBS_DIR" "$ASYNC_OBS_DIR" "$VTRACE_OBS_DIR" \
    "$SERVE_OBS_DIR" "$SOAK_OBS_DIR" "$CHAOS_SOAK_OBS_DIR" \
    "$CHAOS_FLOG_DIR" \
    "$CHAOS_JSON" "$SERVE_JSON" "$SOAK_JSON" "$CHAOS_SOAK_JSON" \
    "$TRACE_JSON" \
    "$MATRIX_OBS_DIR" "$MATRIX_CKPT_DIR" "$MATRIX_CLEAN_DIR" \
    "$MATRIX_JSON" \
    "$MESH_OBS_DIR" "$PBT_OBS_DIR" "$MESH_JSON" "$PBT_JSON"' EXIT
# JAX_ENABLE_COMPILATION_CACHE=false on BOTH mesh trains: the persistent
# compile cache flakily heap-corrupts (malloc_consolidate / segfault,
# ~25% of runs) when it round-trips a MULTI-device SPMD executable on
# the forced-multi-device CPU backend (jax 0.4.37; single-device
# programs — every other stage here — are unaffected). The mesh smokes
# recompile from scratch each run; ~2 min extra, deterministic green.
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    JAX_ENABLE_COMPILATION_CACHE=false \
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    python -m rlgpuschedule_tpu.train --config ppo-mlp-synth64 \
    --mesh auto \
    --iterations 3 --n-envs 4 --n-nodes 2 --gpus-per-node 4 \
    --window-jobs 16 --horizon 64 --queue-len 4 --n-steps 8 \
    --n-epochs 1 --n-minibatches 2 --log-every 1 \
    --obs-dir "$MESH_OBS_DIR" --alarms > "$MESH_JSON"
timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.obs.report "$MESH_OBS_DIR" --strict-alarms
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    JAX_ENABLE_COMPILATION_CACHE=false \
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    python -m rlgpuschedule_tpu.train --config ppo-mlp-synth64 \
    --pbt --n-pop 2 --pbt-ready 1 \
    --iterations 3 --n-envs 4 --n-nodes 2 --gpus-per-node 4 \
    --window-jobs 16 --horizon 64 --queue-len 4 --n-steps 8 \
    --n-epochs 1 --n-minibatches 2 --log-every 1 \
    --obs-dir "$PBT_OBS_DIR" --alarms > "$PBT_JSON"
timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.obs.report "$PBT_OBS_DIR" --strict-alarms
python - "$MESH_JSON" "$PBT_JSON" <<'EOF'
import json, sys
mesh = json.load(open(sys.argv[1]))["mesh"]
assert mesh["shape"] == {"pop": 1, "data": 2, "model": 1}, mesh
assert len(mesh["rule_table_hash"]) == 12, mesh
pbt = json.load(open(sys.argv[2]))["mesh"]
assert pbt["shape"] == {"pop": 2, "data": 1, "model": 1}, pbt
assert pbt["rule_table_hash"] == mesh["rule_table_hash"], (mesh, pbt)
print("sharding smoke ok:", {"mesh": mesh["shape"], "pbt": pbt["shape"],
                             "rules": mesh["rule_table_hash"]})
EOF

echo "=== smoke: data flywheel (flight log -> continual retrain -> canary promotion, 2 CPU devices) ==="
# ISSUE 19 acceptance, the closed loop end to end: (1) a routed soak
# with the durable flight log attached seals crc-sidecar'd shards and
# holds the conservation contract (rows_logged == served, exactly);
# (2) train --continual ingests those shards through the V-trace
# trust region (zero refusals on fresh same-policy traffic) and steps
# the learner; (3) an intentionally-regressed candidate (seeded noise
# that flips decisions on the logged states) must be BLOCKED by the
# canary gate; (4) a clean candidate must promote with ZERO swap
# recompiles, then the forced post-swap SLO fault must roll back and
# restore the incumbent bit-identically — all three verdicts sealed in
# the crc'd promotion ledger, every obs dir strict-alarms green (the
# flywheel's event kinds are not alarm kinds).
FLY_DIR=$(mktemp -d /tmp/ci_flywheel.XXXXXX)
trap 'rm -rf "$OBS_DIR" "$ASYNC_OBS_DIR" "$VTRACE_OBS_DIR" \
    "$SERVE_OBS_DIR" "$SOAK_OBS_DIR" "$CHAOS_SOAK_OBS_DIR" \
    "$CHAOS_FLOG_DIR" \
    "$CHAOS_JSON" "$SERVE_JSON" "$SOAK_JSON" "$CHAOS_SOAK_JSON" \
    "$TRACE_JSON" \
    "$MATRIX_OBS_DIR" "$MATRIX_CKPT_DIR" "$MATRIX_CLEAN_DIR" \
    "$MATRIX_JSON" \
    "$MESH_OBS_DIR" "$PBT_OBS_DIR" "$MESH_JSON" "$PBT_JSON" \
    "$FLY_DIR"' EXIT
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    python -m rlgpuschedule_tpu.serve --config ppo-mlp-synth64 \
    --engines 2 --soak 5 --rate 120 --deadline-ms 250 \
    --adaptive-wait --bucket 8 --pool-steps 2 \
    --n-envs 2 --n-nodes 2 --gpus-per-node 4 --window-jobs 16 \
    --queue-len 4 --horizon 64 \
    --flight-log "$FLY_DIR/flog" --flight-capacity 32 --durable-log \
    --obs-dir "$FLY_DIR/obs_soak" > "$FLY_DIR/soak.json"
timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.obs.report "$FLY_DIR/obs_soak" \
    --strict-alarms > /dev/null
python - "$FLY_DIR" <<'EOF'
import json, sys
fly = sys.argv[1]
rep = json.load(open(fly + "/soak.json"))
s, fl = rep["soak"], rep["flight_log"]
# the flywheel's conservation contract: every served row logged, shed
# rows never logged — rows_logged == served EXACTLY
assert fl["conservation_ok"], fl
assert fl["rows_logged"] == s["served"] > 0, (fl, s["served"])
assert s["post_warmup_recompiles"] == 0, s
from rlgpuschedule_tpu.obs import merge_dir
seals = [e for e in merge_dir(fly + "/obs_soak")
         if e["kind"] == "flywheel_shard_seal"]
assert seals and sum(e["rows"] for e in seals) == fl["rows_logged"], \
    (len(seals), fl)
prom = open(fly + "/obs_soak/metrics.prom").read()
for name in ("flywheel_rows_logged_total", "flywheel_shards_sealed_total"):
    assert name in prom, f"missing scrape series: {name}"
# crc-verify every sealed shard through the reader itself
from rlgpuschedule_tpu.flywheel import read_flight_log
data = read_flight_log(fly + "/flog")
assert not data.torn_tail and data.rows == fl["rows_logged"], \
    (data.torn_tail, data.rows)
print("flight-log smoke ok:", {"served": s["served"],
                               "rows_logged": fl["rows_logged"],
                               "shards": len(data.shards)})
EOF
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.train --config ppo-mlp-synth64 \
    --continual "$FLY_DIR/flog" --iterations 2 \
    --n-envs 2 --n-nodes 2 --gpus-per-node 4 \
    --window-jobs 16 --horizon 64 --queue-len 4 --n-steps 8 \
    --n-epochs 1 --n-minibatches 2 \
    --obs-dir "$FLY_DIR/obs_cont" --ckpt-dir "$FLY_DIR/ckpt" \
    > "$FLY_DIR/cont.json"
python - "$FLY_DIR" <<'EOF'
import json, sys
fly = sys.argv[1]
s = json.load(open(fly + "/cont.json"))
assert s["mode"] == "continual", s["mode"]
# fresh same-policy traffic sits at rho ~ 1: the trust region must
# admit every shard, and two V-trace iterations must step the learner
assert s["shards_seen"] > 0 and s["shards_refused"] == 0, s
assert s["shards_accepted"] == s["shards_seen"], s
assert not s["torn_tail"], s
assert s["rows_trained"] > 0 and s["final_step"] > 0, s
assert 0.5 < s["rho_mean_trained"] < 2.0, s["rho_mean_trained"]
prom = open(fly + "/obs_cont/metrics.prom").read()
for name in ("flywheel_shard_staleness", "flywheel_rho_mean",
             "flywheel_rho_max", "flywheel_shards_ingested_total",
             "flywheel_shards_refused_total"):
    assert name in prom, f"missing scrape series: {name}"
print("continual smoke ok:", {
    "shards": f"{s['shards_accepted']}/{s['shards_seen']}",
    "rows_trained": s["rows_trained"],
    "pseudo_steps": s["pseudo_steps"],
    "final_step": s["final_step"],
    "rho_mean": round(s["rho_mean_trained"], 4)})
EOF
# (3) the regressed arm: sigma 0.5 on the config seed flips the served
# decision on the logged multi-legal-action states — the canary gate
# must block it (the whole pipeline is seeded, so this is
# deterministic, not a coin flip)
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.serve --config ppo-mlp-synth64 \
    --bucket 8 --pool-steps 2 --n-envs 2 --n-nodes 2 \
    --gpus-per-node 4 --window-jobs 16 --queue-len 4 --horizon 64 \
    --flight-log "$FLY_DIR/flog" --durable-log --promote-noise 0.5 \
    --obs-dir "$FLY_DIR/obs_block" > "$FLY_DIR/block.json"
# (4) the clean arm: a numerically-indistinguishable candidate clears
# the gate, promotes with zero swap recompiles, then the forced SLO
# fault must roll it back and restore the incumbent bit-identically
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.serve --config ppo-mlp-synth64 \
    --bucket 8 --pool-steps 2 --n-envs 2 --n-nodes 2 \
    --gpus-per-node 4 --window-jobs 16 --queue-len 4 --horizon 64 \
    --flight-log "$FLY_DIR/flog" --durable-log --promote-noise 1e-6 \
    --promote-fault \
    --obs-dir "$FLY_DIR/obs_prom" > "$FLY_DIR/promote.json"
timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.obs.report "$FLY_DIR/obs_block" \
    --strict-alarms > /dev/null
timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python -m rlgpuschedule_tpu.obs.report "$FLY_DIR/obs_prom" \
    --strict-alarms > /dev/null
python - "$FLY_DIR" <<'EOF'
import json, sys
fly = sys.argv[1]
blk = json.load(open(fly + "/block.json"))["promote"]
assert blk["verdict"] == "blocked" and not blk["promoted"], blk
assert blk["canary"]["max_regress_streak"] >= 2, blk["canary"]
pro = json.load(open(fly + "/promote.json"))["promote"]
assert pro["verdict"] == "promote" and pro["promoted"], pro
assert pro["swap_recompiles"] == 0, pro
assert pro["post_warmup_recompiles"] == 0, pro
assert pro["rollback"], pro
# the rollback restored the incumbent EXACTLY: the pre-promotion probe
# decisions replay bit-identically after the swap back
assert pro["probe_bit_identical"] is True, pro
# promotion lineage: blocked + promote + rollback, all crc-sealed
from rlgpuschedule_tpu.flywheel import read_ledger
sealed, tail = read_ledger(fly + "/flog")
assert [e["action"] for e in sealed] == \
    ["blocked", "promote", "rollback"], [e["action"] for e in sealed]
assert not tail, tail
from rlgpuschedule_tpu.obs import merge_dir
kinds_blk = {e["kind"] for e in merge_dir(fly + "/obs_block")}
kinds_pro = {e["kind"] for e in merge_dir(fly + "/obs_prom")}
assert "promote_blocked" in kinds_blk, sorted(kinds_blk)
for k in ("promote_apply", "promote_rollback"):
    assert k in kinds_pro, sorted(kinds_pro)
prom = open(fly + "/obs_block/metrics.prom").read()
for name in ("flywheel_canary_runs_total",
             "flywheel_promotions_blocked_total"):
    assert name in prom, f"missing scrape series: {name}"
print("promotion smoke ok:", {
    "blocked_agreement": round(blk["canary"]["candidate_agreement"], 3),
    "promoted": pro["candidate"],
    "rollback_reasons": pro["rollback_reasons"],
    "probe_bit_identical": pro["probe_bit_identical"],
    "ledger": [e["action"] for e in sealed]})
EOF

echo "=== tier-1 pytest gate 1/2: main pass (ROADMAP.md, minus spawn) ==="
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow and not multihost_spawn' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
    -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)
[ "$rc" -eq 0 ] || exit $rc

echo "=== tier-1 pytest gate 2/2: multihost spawn subset (serial) ==="
rm -f /tmp/_t1_spawn.log
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow and multihost_spawn' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
    -p no:randomly 2>&1 | tee /tmp/_t1_spawn.log
rc=${PIPESTATUS[0]}
echo SPAWN_DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' \
    /tmp/_t1_spawn.log | tr -cd . | wc -c)
exit $rc
