#!/usr/bin/env bash
# Pre-push gate: the speclint static analyzer (Passes 1-5) plus smoke
# runs of every gated subsystem.  Fast (no model checking beyond toy
# configs, no kernel compiles beyond the analyzer's own imports) — run
# it before every push:
#
#     tools/lint.sh            # both encoding modes, flagship cfg
#     tools/lint.sh --strict   # warnings fail too
#
# Exits nonzero if the analyzer reports an error (or, with --strict, any
# finding), or if any smoke block fails.  Every block is named: the
# summary table at the end shows one line per block, and a mid-script
# failure prints "FAILED in block: <name>" so it cannot be misread.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

SERVE_TMP=""
BLOCK_NAMES=()
BLOCK_STATUS=()
CURRENT_BLOCK=""

begin() {
    # close the previous block as ok (a failure never reaches the next
    # begin under set -e), then open the named one
    if [ -n "$CURRENT_BLOCK" ]; then
        BLOCK_NAMES+=("$CURRENT_BLOCK"); BLOCK_STATUS+=("ok")
    fi
    CURRENT_BLOCK="$1"
    echo "== $2 =="
}

on_exit() {
    rc=$?
    [ -n "$SERVE_TMP" ] && rm -rf "$SERVE_TMP"
    if [ -n "$CURRENT_BLOCK" ]; then
        BLOCK_NAMES+=("$CURRENT_BLOCK")
        if [ "$rc" -eq 0 ]; then BLOCK_STATUS+=("ok")
        else BLOCK_STATUS+=("FAIL"); fi
    fi
    echo
    echo "== lint.sh summary =="
    for ((i = 0; i < ${#BLOCK_NAMES[@]}; i++)); do
        printf '  %-14s %s\n' "${BLOCK_NAMES[$i]}" "${BLOCK_STATUS[$i]}"
    done
    if [ "$rc" -ne 0 ]; then
        echo "FAILED in block: $CURRENT_BLOCK (exit $rc)"
    else
        echo "all ${#BLOCK_NAMES[@]} blocks ok"
    fi
    exit "$rc"
}
trap on_exit EXIT

begin speclint "speclint (width + cfg + jit + thread + contract, parity & faithful)"
python -m raft_tla_tpu.lint runs/MC3s2v.cfg "$@"

begin collect "pytest smoke collection"
python -m pytest tests/ -m smoke --collect-only -q -p no:cacheprovider \
    --continue-on-collection-errors | tail -2

begin obs "obs smoke (event schema conformance)"
python -m pytest tests/test_obs.py -m smoke -q -p no:cacheprovider | tail -2

begin serve "serve smoke (2-job toy manifest end-to-end, CPU)"
SERVE_TMP="$(mktemp -d)"
cat > "$SERVE_TMP/toy.cfg" <<'CFG'
SPECIFICATION Spec
INVARIANT NoTwoLeaders
CONSTANTS
    Server = {s1, s2}
    Value = {v1}
    Follower = "Follower"
    Candidate = "Candidate"
    Leader = "Leader"
    Nil = "Nil"
    RequestVoteRequest = "RequestVoteRequest"
    RequestVoteResponse = "RequestVoteResponse"
    AppendEntriesRequest = "AppendEntriesRequest"
    AppendEntriesResponse = "AppendEntriesResponse"
CFG
cat > "$SERVE_TMP/manifest.jsonl" <<'MANIFEST'
{"id": "smoke-a", "cfg": "toy.cfg", "spec": "election", "max_term": 2, "max_log": 0, "max_msgs": 2}
{"id": "smoke-b", "cfg": "toy.cfg", "spec": "election", "max_term": 2, "max_log": 0, "max_msgs": 2}
MANIFEST
python -m raft_tla_tpu.serve "$SERVE_TMP/manifest.jsonl" \
    --out "$SERVE_TMP/out" --chunk 256 --cpu --quiet
python - "$SERVE_TMP/out" <<'PY'
import json, sys
out = sys.argv[1]
recs = [json.loads(l) for l in open(f"{out}/results.jsonl")]
assert len(recs) == 2 and all(r["status"] == "completed" for r in recs), recs
assert all(r["n_states"] == 3014 for r in recs), recs
from raft_tla_tpu.obs import validate_event
for r in recs:
    events = [json.loads(l) for l in open(r["events"])]
    assert not [e for d in events for e in validate_event(d)]
    assert events[-1]["event"] == "run_end" and events[-1]["outcome"] == "ok"
print(f"serve smoke ok: 2 jobs x {recs[0]['n_states']} states, "
      "per-tenant event logs valid")
PY

begin serve-daemon "serve daemon smoke (watch-dir intake -> SIGINT drain, CPU)"
mkdir -p "$SERVE_TMP/queue"
python -m raft_tla_tpu.serve "$SERVE_TMP/queue" --watch \
    --out "$SERVE_TMP/dout" --chunk 64 --poll 0.2 --cpu --quiet &
DAEMON_PID=$!
cat > "$SERVE_TMP/queue/001-watched.json" <<'JOB'
{"id": "watched", "cfg": "../toy.cfg", "spec": "election", "max_term": 2, "max_log": 0, "max_msgs": 1}
JOB
for _ in $(seq 1 600); do
    grep -q '"job_id": "watched"' "$SERVE_TMP/dout/results.jsonl" \
        2>/dev/null && break
    kill -0 "$DAEMON_PID" 2>/dev/null || { echo "daemon died early"; exit 1; }
    sleep 0.3
done
kill -INT "$DAEMON_PID"
wait "$DAEMON_PID" || { echo "daemon SIGINT drain exited nonzero"; exit 1; }
python - "$SERVE_TMP/dout" <<'PY'
import json, sys
recs = [json.loads(l) for l in open(f"{sys.argv[1]}/results.jsonl")]
(rec,) = [r for r in recs if r["job_id"] == "watched"]
assert rec["status"] == "completed" and rec["n_states"] == 524, rec
print("serve daemon smoke ok: watch intake served, SIGINT drained clean")
PY

begin serve-chaos "serve-chaos smoke (worker pool + mid-dispatch SIGKILL, CPU)"
# The pool's acceptance bar in miniature: solo reference pass, then the
# supervised worker pool with the first worker SIGKILLed after 2 segment
# events — requeued jobs re-run losslessly and every final results
# record and tenant event log must be canonically identical to solo.
python -m raft_tla_tpu.serve.chaos "$SERVE_TMP/toy.cfg" \
    --workdir "$SERVE_TMP/serve-chaos" --jobs 4 --workers 2 \
    --chunk 256 --max-msgs 1 --kill-after-segments 2 --cpu --quiet \
    | tail -1

begin frontend "frontend smoke (two-phase commit through the spec compiler, CPU)"
cat > "$SERVE_TMP/2pc.cfg" <<'CFG'
SPECIFICATION Spec
CONSTANT RM = {r1, r2}
INVARIANT TCConsistent
CFG
python -m raft_tla_tpu.check "$SERVE_TMP/2pc.cfg" \
    --spec twophase --engine host --chunk 256 --cpu \
    | tee "$SERVE_TMP/2pc.out" | tail -2
grep -q "^56 distinct states found" "$SERVE_TMP/2pc.out" \
    || { echo "frontend smoke FAILED: expected 56 states"; exit 1; }
# ... and single-decree Paxos: a set-of-sets CONSTANT, `<-` recorded
printf 'CONSTANTS\n  Acceptor = {a1, a2, a3}\n  Value = {v1, v2}\n  Quorum = {{a1, a2}, {a1, a3}, {a2, a3}}\n  None = None\n  Ballot <- MCBallot\nSPECIFICATION Spec\nINVARIANTS TypeOK Consistency\n' \
    > "$SERVE_TMP/MCPaxos.cfg"
python -m raft_tla_tpu.check "$SERVE_TMP/MCPaxos.cfg" \
    --spec paxos --engine ddd --max-term 1 --chunk 64 --cpu \
    | tee "$SERVE_TMP/paxos.out" | tail -2
grep -q "^3921 distinct states found, diameter 16, 22994 transitions" \
    "$SERVE_TMP/paxos.out" \
    || { echo "frontend smoke FAILED: expected 3921 Paxos states"; exit 1; }

begin host-dedup "host-dedup smoke (ddd engine, background partitioned flush, CPU)"
# Gate forced ON: the toy cfg runs end-to-end through the ddd engine
# with partitioned master keys and the depth-1 background flush worker,
# then again with the gate OFF — the result lines (counts, diameter,
# transitions; wall stripped) must be byte-identical.
python -m raft_tla_tpu.check "$SERVE_TMP/toy.cfg" \
    --spec election --max-term 2 --max-log 0 --max-msgs 2 \
    --engine ddd --chunk 32 --host-dedup on --cpu --no-lint --no-trace \
    | tee "$SERVE_TMP/hostdedup_on.out" | tail -2
grep -q "^3014 distinct states found" "$SERVE_TMP/hostdedup_on.out" \
    || { echo "host-dedup smoke FAILED: expected 3014 states"; exit 1; }
python -m raft_tla_tpu.check "$SERVE_TMP/toy.cfg" \
    --spec election --max-term 2 --max-log 0 --max-msgs 2 \
    --engine ddd --chunk 32 --host-dedup off --cpu --no-lint --no-trace \
    > "$SERVE_TMP/hostdedup_off.out"
on_line="$(grep '^3014 distinct states found' "$SERVE_TMP/hostdedup_on.out" \
    | sed 's/, [0-9.]*s.*//')"
off_line="$(grep '^3014 distinct states found' "$SERVE_TMP/hostdedup_off.out" \
    | sed 's/, [0-9.]*s.*//')"
[ "$on_line" = "$off_line" ] \
    || { echo "host-dedup smoke FAILED: on/off result lines differ"; \
         echo "  on:  $on_line"; echo "  off: $off_line"; exit 1; }
echo "host-dedup smoke ok: on/off byte-identical ($on_line)"

begin prefetch "prefetch smoke (ddd engine, double-buffered upload staging, CPU)"
# Gate forced ON: the toy cfg runs end-to-end through the ddd engine
# with block uploads served from the background prefetch thread, then
# again with the gate OFF — the result lines (counts, diameter,
# transitions; wall stripped) must be byte-identical.
python -m raft_tla_tpu.check "$SERVE_TMP/toy.cfg" \
    --spec election --max-term 2 --max-log 0 --max-msgs 2 \
    --engine ddd --chunk 32 --prefetch on --cpu --no-lint --no-trace \
    | tee "$SERVE_TMP/prefetch_on.out" | tail -2
grep -q "^3014 distinct states found" "$SERVE_TMP/prefetch_on.out" \
    || { echo "prefetch smoke FAILED: expected 3014 states"; exit 1; }
python -m raft_tla_tpu.check "$SERVE_TMP/toy.cfg" \
    --spec election --max-term 2 --max-log 0 --max-msgs 2 \
    --engine ddd --chunk 32 --prefetch off --cpu --no-lint --no-trace \
    > "$SERVE_TMP/prefetch_off.out"
on_line="$(grep '^3014 distinct states found' "$SERVE_TMP/prefetch_on.out" \
    | sed 's/, [0-9.]*s.*//')"
off_line="$(grep '^3014 distinct states found' "$SERVE_TMP/prefetch_off.out" \
    | sed 's/, [0-9.]*s.*//')"
[ "$on_line" = "$off_line" ] \
    || { echo "prefetch smoke FAILED: on/off result lines differ"; \
         echo "  on:  $on_line"; echo "  off: $off_line"; exit 1; }
echo "prefetch smoke ok: on/off byte-identical ($on_line)"

begin device-dedup "device-dedup smoke (ddd engine, HBM within-level exact set, CPU)"
# Gate forced ON (hash backend): the toy cfg runs end-to-end through
# the ddd engine with the device-resident within-level fingerprint set
# filtering segment exports, then again with the gate OFF — the result
# lines (counts, diameter, transitions; wall stripped) must be
# byte-identical (the widening contract: the set only drops rows the
# host master keyset would reject anyway).
python -m raft_tla_tpu.check "$SERVE_TMP/toy.cfg" \
    --spec election --max-term 2 --max-log 0 --max-msgs 2 \
    --engine ddd --chunk 32 --device-dedup on --cpu --no-lint --no-trace \
    | tee "$SERVE_TMP/devdedup_on.out" | tail -2
grep -q "^3014 distinct states found" "$SERVE_TMP/devdedup_on.out" \
    || { echo "device-dedup smoke FAILED: expected 3014 states"; exit 1; }
python -m raft_tla_tpu.check "$SERVE_TMP/toy.cfg" \
    --spec election --max-term 2 --max-log 0 --max-msgs 2 \
    --engine ddd --chunk 32 --device-dedup off --cpu --no-lint --no-trace \
    > "$SERVE_TMP/devdedup_off.out"
on_line="$(grep '^3014 distinct states found' "$SERVE_TMP/devdedup_on.out" \
    | sed 's/, [0-9.]*s.*//')"
off_line="$(grep '^3014 distinct states found' "$SERVE_TMP/devdedup_off.out" \
    | sed 's/, [0-9.]*s.*//')"
[ "$on_line" = "$off_line" ] \
    || { echo "device-dedup smoke FAILED: on/off result lines differ"; \
         echo "  on:  $on_line"; echo "  off: $off_line"; exit 1; }
echo "device-dedup smoke ok: on/off byte-identical ($on_line)"

begin gates "gates smoke (--prescan/--phase-timers/--compile-cache, CPU)"
# The three remaining RAFT_TLA_* gates exercised in one identity check:
# every gate forced away from its auto default (the phase-timer sync
# path, the prescan ladder, the persistent compile cache), then a
# default run — the result lines (wall stripped) must be byte-identical,
# and the compile cache directory must actually be populated.
python -m raft_tla_tpu.check "$SERVE_TMP/toy.cfg" \
    --spec election --max-term 2 --max-log 0 --max-msgs 2 \
    --engine ddd --chunk 32 --prescan on \
    --phase-timers --compile-cache "$SERVE_TMP/jaxcache" \
    --cpu --no-lint --no-trace \
    | tee "$SERVE_TMP/gates_on.out" | tail -2
grep -q "^3014 distinct states found" "$SERVE_TMP/gates_on.out" \
    || { echo "gates smoke FAILED: expected 3014 states"; exit 1; }
[ -d "$SERVE_TMP/jaxcache" ] && [ -n "$(ls -A "$SERVE_TMP/jaxcache")" ] \
    || { echo "gates smoke FAILED: compile cache dir empty"; exit 1; }
python -m raft_tla_tpu.check "$SERVE_TMP/toy.cfg" \
    --spec election --max-term 2 --max-log 0 --max-msgs 2 \
    --engine ddd --chunk 32 --prescan off \
    --cpu --no-lint --no-trace \
    > "$SERVE_TMP/gates_off.out"
on_line="$(grep '^3014 distinct states found' "$SERVE_TMP/gates_on.out" \
    | sed 's/, [0-9.]*s.*//')"
off_line="$(grep '^3014 distinct states found' "$SERVE_TMP/gates_off.out" \
    | sed 's/, [0-9.]*s.*//')"
[ "$on_line" = "$off_line" ] \
    || { echo "gates smoke FAILED: on/off result lines differ"; \
         echo "  on:  $on_line"; echo "  off: $off_line"; exit 1; }
echo "gates smoke ok: on/off byte-identical ($on_line)"

begin trace "trace smoke (v8 spans -> collect -> Perfetto -> report, CPU)"
# Tracing forced ON: the toy cfg runs through the ddd engine with span
# emission into the event log, the trace CLI must collect, export and
# attribute it — then the same run with tracing OFF must produce a
# byte-identical result line (the off-path discipline in one grep).
python -m raft_tla_tpu.check "$SERVE_TMP/toy.cfg" \
    --spec election --max-term 2 --max-log 0 --max-msgs 2 \
    --engine ddd --chunk 32 --host-dedup on --prefetch on \
    --events "$SERVE_TMP/trace.events" --trace \
    --cpu --no-lint --no-trace \
    | tee "$SERVE_TMP/trace_on.out" | tail -2
grep -q "^3014 distinct states found" "$SERVE_TMP/trace_on.out" \
    || { echo "trace smoke FAILED: expected 3014 states"; exit 1; }
grep -q '"event": "span"' "$SERVE_TMP/trace.events" \
    || { echo "trace smoke FAILED: no span events in the log"; exit 1; }
python -m raft_tla_tpu.obs.tracecli collect "$SERVE_TMP/trace.events"
python -m raft_tla_tpu.obs.tracecli export "$SERVE_TMP/trace.events" \
    -o "$SERVE_TMP/trace.json"
python -c "import json,sys; d=json.load(open(sys.argv[1])); \
    assert any(e['ph'] == 'X' for e in d['traceEvents']), 'no spans'" \
    "$SERVE_TMP/trace.json"
python -m raft_tla_tpu.obs.tracecli report "$SERVE_TMP/trace.events" \
    > "$SERVE_TMP/trace_report.out"
head -8 "$SERVE_TMP/trace_report.out"
python -m raft_tla_tpu.check "$SERVE_TMP/toy.cfg" \
    --spec election --max-term 2 --max-log 0 --max-msgs 2 \
    --engine ddd --chunk 32 --host-dedup on --prefetch on \
    --events "$SERVE_TMP/plain.events" \
    --cpu --no-lint --no-trace \
    > "$SERVE_TMP/trace_off.out"
# untraced, the log holds no span but its run_end carries the pass ledger's
# record (always on), and the report prints the level table from it
if grep -q '"event": "span"' "$SERVE_TMP/plain.events"; then
    echo "trace smoke FAILED: an untraced log holds spans"; exit 1
fi
python -m raft_tla_tpu.obs.tracecli report "$SERVE_TMP/plain.events" \
    | grep -c "^  L[0-9]*: " | grep -qx 18 \
    || { echo "trace smoke FAILED: no level table from run_end.level_log"; \
         exit 1; }
on_line="$(grep '^3014 distinct states found' "$SERVE_TMP/trace_on.out" \
    | sed 's/, [0-9.]*s.*//')"
off_line="$(grep '^3014 distinct states found' "$SERVE_TMP/trace_off.out" \
    | sed 's/, [0-9.]*s.*//')"
[ "$on_line" = "$off_line" ] \
    || { echo "trace smoke FAILED: on/off result lines differ"; \
         echo "  on:  $on_line"; echo "  off: $off_line"; exit 1; }
echo "trace smoke ok: on/off byte-identical ($on_line)"

begin campaign-chaos "chaos smoke (campaign SIGKILL + reshard 1->2->1, CPU)"
# The campaign supervisor's acceptance loop in miniature: reference run,
# then SIGKILL after the 2nd checkpoint, auto-reshard across a 1->2->1
# virtual-mesh plan, unattended resume — finals must be identical.
python -m raft_tla_tpu.campaign.chaos "$SERVE_TMP/toy.cfg" \
    --workdir "$SERVE_TMP/campaign" --spec election \
    --max-term 2 --max-log 0 --max-msgs 2 \
    --window 128 --chunk 32 --kill-after 2 --mesh-plan 1,2,1 --cpu \
    | tail -3

begin fleet "fleet smoke (sharded walker fleet, 2 virtual devices, CPU)"
# Deterministic seed: the same cfg at the same seed must report the same
# behavior/state counts every run, on any mesh (the fleet's
# device-count-invariance contract in one grep).
python -m raft_tla_tpu.check "$SERVE_TMP/toy.cfg" \
    --engine ref --spec election --max-term 2 --max-log 0 --max-msgs 2 \
    --simulate 200 --depth 20 --walkers 64 --seed 5 \
    --fleet --devices 2 --cpu \
    | tee "$SERVE_TMP/fleet.out" | tail -4
grep -q "^Fleet: 2 devices x 32 walkers" "$SERVE_TMP/fleet.out" \
    || { echo "fleet smoke FAILED: no fleet summary"; exit 1; }
grep -q "behaviors generated" "$SERVE_TMP/fleet.out" \
    || { echo "fleet smoke FAILED: no behaviors line"; exit 1; }

begin metrics "metrics smoke (OpenMetrics endpoint + v10 snapshot, gate on/off identity, CPU)"
# Gate forced ON (--metrics-port 0 = ephemeral port; RAFT_TLA_METRICS
# is the equivalent process-wide switch): the toy manifest runs
# one-pass with the endpoint up, and every stable result field must be
# identical to the gate-off serve block's records — the endpoint is a
# pure log reader.  Then the watch daemon with a 2-worker pool: scrape
# the live endpoint (per-tenant p99 latency summary, queue depth, pool
# worker counters), SIGINT drain, and the replayable
# OUT/metrics.events snapshot log must validate as schema v10.
python -m raft_tla_tpu.serve "$SERVE_TMP/manifest.jsonl" \
    --out "$SERVE_TMP/mout" --chunk 256 --metrics-port 0 --cpu --quiet \
    | tee "$SERVE_TMP/metrics_serve.out"
grep -q "^metrics endpoint: http://127.0.0.1:" \
    "$SERVE_TMP/metrics_serve.out" \
    || { echo "metrics smoke FAILED: no endpoint line"; exit 1; }
python - "$SERVE_TMP/out" "$SERVE_TMP/mout" <<'PY'
import json, sys
VOLATILE = ("admission_s", "wall_s", "states_per_sec", "events")
def canon(out):
    recs = [json.loads(l) for l in open(f"{out}/results.jsonl")]
    return sorted(
        json.dumps({k: v for k, v in r.items() if k not in VOLATILE},
                   sort_keys=True) for r in recs)
off, on = canon(sys.argv[1]), canon(sys.argv[2])
assert off == on, f"gate on/off result records differ:\n{off}\n{on}"
print("metrics one-pass ok: gate on/off result records identical")
PY
mkdir -p "$SERVE_TMP/mqueue"
python -m raft_tla_tpu.serve "$SERVE_TMP/mqueue" --watch --workers 2 \
    --out "$SERVE_TMP/mdout" --chunk 64 --poll 0.2 --metrics-port 0 \
    --cpu --quiet > "$SERVE_TMP/mdaemon.out" &
MDAEMON_PID=$!
cat > "$SERVE_TMP/mqueue/001-mjob.json" <<'JOB'
{"id": "mjob", "cfg": "../toy.cfg", "spec": "election", "max_term": 2, "max_log": 0, "max_msgs": 1}
JOB
for _ in $(seq 1 600); do
    grep -q '"job_id": "mjob"' "$SERVE_TMP/mdout/results.jsonl" \
        2>/dev/null && break
    kill -0 "$MDAEMON_PID" 2>/dev/null \
        || { echo "metrics daemon died early"; exit 1; }
    sleep 0.3
done
MPORT="$(sed -n \
    's|^metrics endpoint: http://127.0.0.1:\([0-9]*\)/metrics$|\1|p' \
    "$SERVE_TMP/mdaemon.out")"
[ -n "$MPORT" ] \
    || { echo "metrics smoke FAILED: no port in daemon output"; exit 1; }
python - "$MPORT" <<'PY'
import sys, urllib.request
body = urllib.request.urlopen(
    f"http://127.0.0.1:{sys.argv[1]}/metrics", timeout=10).read().decode()
assert 'raft_tla_latency_seconds{tenant="mjob",quantile="0.99"}' in body, \
    body
assert "raft_tla_queue_depth" in body, body
assert "raft_tla_workers_spawned_total" in body, body
print("metrics scrape ok: per-tenant p99 latency + queue depth + "
      "pool counters served")
PY
kill -INT "$MDAEMON_PID"
wait "$MDAEMON_PID" \
    || { echo "metrics daemon SIGINT drain exited nonzero"; exit 1; }
python - "$SERVE_TMP/mdout/metrics.events" <<'PY'
import json, sys
from raft_tla_tpu.obs import validate_event
evs = [json.loads(l) for l in open(sys.argv[1])]
assert evs and all(e["event"] == "metrics_snapshot" for e in evs), evs
assert not [err for e in evs for err in validate_event(e)]
print(f"metrics snapshot ok: {len(evs)} schema-v10 snapshot(s) "
      "replayable from the log alone")
PY

begin regress "regress smoke (history ingest -> drift verdicts -> A/B reproduction)"
# The cross-run sentinel end-to-end (--history PATH; RAFT_TLA_HISTORY
# is the equivalent): the recorded BENCH drivers seed the store, the
# same-config round passes clean (exit 0), a planted 10x wall
# regression exits 4, and the recorded devdedup A/B reproduces its
# RESULTS.md refutation verdict mechanically.
python -m raft_tla_tpu.obs.regress ingest BENCH_r0*.json \
    --history "$SERVE_TMP/history.jsonl"
python -m raft_tla_tpu.obs.regress check BENCH_r05.json \
    --history "$SERVE_TMP/history.jsonl" \
    || { echo "regress smoke FAILED: clean re-run did not exit 0"; exit 1; }
python - "$SERVE_TMP/slow.json" <<'PY'
import json, sys
doc = json.load(open("BENCH_r05.json"))
for k, v in list(doc["parsed"].items()):
    if isinstance(v, (int, float)) and not isinstance(v, bool) \
        and ("wall" in k or k.endswith("_ms")):
        doc["parsed"][k] = v * 10.0
json.dump(doc, open(sys.argv[1], "w"))
PY
rc=0
python -m raft_tla_tpu.obs.regress check "$SERVE_TMP/slow.json" \
    --history "$SERVE_TMP/history.jsonl" || rc=$?
[ "$rc" -eq 4 ] \
    || { echo "regress smoke FAILED: planted drift exit $rc != 4"; exit 1; }
rc=0
python -m raft_tla_tpu.obs.regress ab runs/devdedup_ab.out || rc=$?
[ "$rc" -eq 4 ] \
    || { echo "regress smoke FAILED: devdedup ab exit $rc != 4"; exit 1; }
echo "regress smoke ok: clean pass, planted drift caught (exit 4), devdedup refutation reproduced"
