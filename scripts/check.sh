#!/usr/bin/env bash
# Repo smoke check: the static invariant checker, a sanitizer-wired
# native configure/build, native static analysis (clang-tidy or GCC
# -fanalyzer), and the ct_pmux/txn/shrink/service smokes
# (docs/static_analysis.md). Exits non-zero on any violation.
#
# --json: one machine-readable line per stage on stdout
# ({"stage": ..., "ok": true|false, "secs": N}) so automation can gate
# per stage; human banners are suppressed.
set -euo pipefail

cd "$(dirname "$0")/.."

JSON_MODE=0
if [ "${1:-}" = "--json" ]; then
    JSON_MODE=1
    shift
fi

# the repo root joins PYTHONPATH (appended: keep what the caller set)
export PYTHONPATH="${PYTHONPATH:+$PYTHONPATH:}$PWD"

CURRENT_STAGE=""
STAGE_START=$SECONDS
CLEANUP_PIDS=""

json_line() {
    printf '{"stage": "%s", "ok": %s, "secs": %s}\n' "$1" "$2" "$3"
}

stage_end_ok() {
    if [ -n "$CURRENT_STAGE" ] && [ "$JSON_MODE" = 1 ]; then
        json_line "$CURRENT_STAGE" true $((SECONDS - STAGE_START))
    fi
    CURRENT_STAGE=""
}

stage() {            # stage <id> <human banner...>
    stage_end_ok
    CURRENT_STAGE="$1"
    STAGE_START=$SECONDS
    shift
    if [ "$JSON_MODE" = 0 ]; then
        echo "== $* =="
    fi
}

on_exit() {
    rc=$?
    for pid in $CLEANUP_PIDS; do
        kill "$pid" 2>/dev/null || true
    done
    if [ "$rc" -ne 0 ] && [ -n "$CURRENT_STAGE" ] \
            && [ "$JSON_MODE" = 1 ]; then
        json_line "$CURRENT_STAGE" false $((SECONDS - STAGE_START))
    fi
}
trap on_exit EXIT

# In JSON mode stage output moves to stderr (the JSON lines ARE the
# stdout contract) — findings and diagnostics stay visible either way.
run() {
    if [ "$JSON_MODE" = 1 ]; then
        "$@" 1>&2
    else
        "$@"
    fi
}

zombie_count() {
    ps -eo stat= | grep -c '^Z' || true
}

# The zombie accounting is system-wide, so it can TRANSIENTLY exceed
# the stage baseline for reasons outside the process tree under test —
# LeakSanitizer's exit-time tracer briefly shows as a Z child of a
# dying ASan ct_pmux until init lazily reaps the orphan. A leak is a
# count that STAYS elevated: give the table a settle window before
# declaring one. Prints the settled count; exits non-zero if still
# above baseline after ~5s.
zombies_settled() {    # zombies_settled BASELINE
    local base=$1 now=0
    for _ in $(seq 50); do
        now=$(zombie_count)
        if [ "$now" -le "$base" ]; then
            echo "$now"
            return 0
        fi
        sleep 0.1
    done
    echo "$now"
    return 1
}

stage analysis "static invariant checker"
run python -m comdb2_tpu.analysis

stage pack-parity "pack parity smoke (legacy vs columnar ingest)"
# one fixture per corpus family; any segment-stream diff fails CI
# before the slow tier ever runs
run env JAX_PLATFORMS=cpu python scripts/pack_parity_smoke.py

stage asan-build "native configure/build with ASan"
if command -v cmake >/dev/null; then
    cmake -DCT_SANITIZE=address -S native -B native/build-asan \
        >/dev/null
    cmake --build native/build-asan -j"$(nproc)" >/dev/null
else
    # containers without cmake: same flags CT_SANITIZE=address wires
    [ "$JSON_MODE" = 1 ] || \
        echo "cmake not found — direct g++ ASan build of ct_pmux"
    mkdir -p native/build-asan
    g++ -fsanitize=address -fno-omit-frame-pointer -g -Wall -Wextra \
        -Inative/include native/src/pmux_main.cpp \
        -o native/build-asan/ct_pmux -lpthread
fi

stage native-static-analysis \
    "native static analysis (clang-tidy or GCC -fanalyzer)"
# clang-tidy findings fail the build itself (warnings-as-errors);
# -fanalyzer emits warnings, so the build log is grepped — any
# -Wanalyzer finding in ct_pmux/sut_node/client sources fails here
TIDY_LOG=$(mktemp)
if command -v cmake >/dev/null; then
    cmake -DCT_STATIC_ANALYZER=ON -S native -B native/build-tidy \
        >/dev/null
    if ! cmake --build native/build-tidy -j"$(nproc)" \
            >"$TIDY_LOG" 2>&1; then
        tail -40 "$TIDY_LOG" >&2
        echo "native static analysis build failed" >&2
        rm -f "$TIDY_LOG"
        exit 1
    fi
else
    : >"$TIDY_LOG"
    for src in native/src/*.cpp; do
        if ! g++ -fanalyzer -Wall -Wextra -Inative/include -c "$src" \
                -o /tmp/ct_analyze.o >>"$TIDY_LOG" 2>&1; then
            tail -40 "$TIDY_LOG" >&2
            echo "native static analysis: $src failed to compile" >&2
            rm -f "$TIDY_LOG" /tmp/ct_analyze.o
            exit 1
        fi
    done
    rm -f /tmp/ct_analyze.o
fi
if grep -E '\-Wanalyzer|warning:.*\[(bugprone|clang-analyzer|performance)' \
        "$TIDY_LOG" >&2; then
    echo "native static analysis found issues (log above)" >&2
    rm -f "$TIDY_LOG"
    exit 1
fi
rm -f "$TIDY_LOG"

stage pmux-smoke "ct_pmux start/exit under ASan"
PMUX=native/build-asan/ct_pmux
PORT=${CT_CHECK_PMUX_PORT:-15105}
# halt_on_error so a shutdown race fails the script, not just logs
ASAN_OPTIONS=halt_on_error=1 "$PMUX" -p "$PORT" &
PMUX_PID=$!
CLEANUP_PIDS="$PMUX_PID"
for _ in $(seq 50); do
    if bash -c "true >/dev/tcp/127.0.0.1/$PORT" 2>/dev/null; then
        break
    fi
    sleep 0.1
done
exec 3<>"/dev/tcp/127.0.0.1/$PORT"
printf 'hello\nexit\n' >&3
cat <&3 >/dev/null || true
exec 3<&- 3>&-
wait "$PMUX_PID"   # non-zero (ASan abort) fails the check
CLEANUP_PIDS=""

stage tsan "ct_pmux start/serve/shutdown under TSan"
# the other half of CT_SANITIZE: the registry serves every connection
# on its own thread, so the races ASan can't see (registry map vs
# serve threads, shutdown drain vs in-flight handlers) only surface
# under TSan — and only with CONCURRENT clients, so the smoke drives
# eight at once before the shutdown
if command -v cmake >/dev/null; then
    cmake -DCT_SANITIZE=thread -S native -B native/build-tsan \
        >/dev/null
    cmake --build native/build-tsan -j"$(nproc)" >/dev/null
else
    [ "$JSON_MODE" = 1 ] || \
        echo "cmake not found — direct g++ TSan build of ct_pmux"
    mkdir -p native/build-tsan
    g++ -fsanitize=thread -fno-omit-frame-pointer -g -Wall -Wextra \
        -Inative/include native/src/pmux_main.cpp \
        -o native/build-tsan/ct_pmux -lpthread
fi
TSAN_PMUX=native/build-tsan/ct_pmux
TSAN_PORT=${CT_CHECK_TSAN_PMUX_PORT:-15107}
TSAN_STATE=$(mktemp)
# halt_on_error: a reported race aborts non-zero and fails the wait
TSAN_OPTIONS=halt_on_error=1 "$TSAN_PMUX" -p "$TSAN_PORT" \
    -f "$TSAN_STATE" &
TSAN_PID=$!
CLEANUP_PIDS="$TSAN_PID"
for _ in $(seq 50); do
    if bash -c "true >/dev/tcp/127.0.0.1/$TSAN_PORT" 2>/dev/null; then
        break
    fi
    sleep 0.1
done
TSAN_CLIENTS=""
for i in $(seq 8); do
    (
        exec 3<>"/dev/tcp/127.0.0.1/$TSAN_PORT"
        printf "reg sut/tsan%s\nget sut/tsan%s\ndel sut/tsan%s\n" \
            "$i" "$i" "$i" >&3
        head -3 <&3 >/dev/null
        exec 3<&- 3>&-
    ) &
    TSAN_CLIENTS="$TSAN_CLIENTS $!"
done
for pid in $TSAN_CLIENTS; do
    wait "$pid"
done
exec 3<>"/dev/tcp/127.0.0.1/$TSAN_PORT"
printf 'exit\n' >&3
cat <&3 >/dev/null || true
exec 3<&- 3>&-
wait "$TSAN_PID"   # non-zero (TSan race report) fails the check
CLEANUP_PIDS=""
rm -f "$TSAN_STATE"

stage txn-smoke "txn serializability checker smoke (host engine)"
# the seeded G2 write-skew fixture MUST be caught (exit 1 = invalid);
# a miss (exit 0) or a give-up (exit 2) fails the repo check — and
# the clean twin must pass, so the detector can't cheat by flagging
# everything
set +e
JAX_PLATFORMS=cpu python -m comdb2_tpu.filetest --txn --backend host \
    tests/fixtures/txn/g2_item.edn >/dev/null
RC_BAD=$?
JAX_PLATFORMS=cpu python -m comdb2_tpu.filetest --txn --backend host \
    tests/fixtures/txn/clean.edn >/dev/null
RC_CLEAN=$?
set -e
if [ "$RC_BAD" -ne 1 ]; then
    echo "txn checker MISSED the seeded G2-item cycle (rc=$RC_BAD)" >&2
    exit 1
fi
if [ "$RC_CLEAN" -ne 0 ]; then
    echo "txn checker flagged the clean fixture (rc=$RC_CLEAN)" >&2
    exit 1
fi

stage shrink-smoke "shrink smoke (seeded stale-read fixture)"
# the fixture plants a single stale read into a write-only history
# (known minimum: ONE read pair); the minimizer must reach it and the
# minimal history must still be INVALID on offline re-check
SHRINK_STORE=$(mktemp -d)
set +e
JAX_PLATFORMS=cpu python -m comdb2_tpu.filetest --shrink \
    --store "$SHRINK_STORE" tests/fixtures/shrink/stale_read.edn \
    >/dev/null
RC_SHRINK=$?
set -e
if [ "$RC_SHRINK" -ne 1 ]; then
    echo "shrink seed fixture not INVALID (rc=$RC_SHRINK)" >&2
    exit 1
fi
MINIMAL=$(ls "$SHRINK_STORE"/shrink/*/minimal.edn 2>/dev/null | head -1)
if [ -z "$MINIMAL" ]; then
    echo "shrink wrote no minimal.edn" >&2
    exit 1
fi
OPS=$(grep -c ':process' "$MINIMAL")
if [ "$OPS" -gt 2 ]; then
    echo "shrink left $OPS ops (known minimum is 2)" >&2
    exit 1
fi
set +e
JAX_PLATFORMS=cpu python -m comdb2_tpu.filetest --backend host \
    "$MINIMAL" >/dev/null
RC_MIN=$?
set -e
if [ "$RC_MIN" -ne 1 ]; then
    echo "minimal.edn re-check rc=$RC_MIN (must still be INVALID)" >&2
    exit 1
fi
rm -rf "$SHRINK_STORE"

stage wl "workload-family checkers smoke (bank/sets/dirty)"
# ISSUE-20 gate, three layers: (1) the checked-in EDN fixtures
# through the filetest CLI — every seeded violation must be caught
# (exit 1) and every clean twin must pass (exit 0), so the detector
# can't cheat in either direction; (2) bench_wl --quick, which
# hard-asserts device/oracle verdict parity per (family, B) cell and
# one dispatch per pow2 bucket before timing, and closes the compile
# guard over every wl program; (3) a daemon kind:"wl" round trip.
WL_FIX=tests/fixtures/wl
WL_BANK_ARGS="--checker bank --wl-n 8 --wl-total 160"
set +e
JAX_PLATFORMS=cpu python -m comdb2_tpu.filetest $WL_BANK_ARGS \
    "$WL_FIX/bank_valid.edn" >/dev/null
RC_BV=$?
JAX_PLATFORMS=cpu python -m comdb2_tpu.filetest $WL_BANK_ARGS \
    "$WL_FIX/bank_wrong_total.edn" >/dev/null
RC_BB=$?
JAX_PLATFORMS=cpu python -m comdb2_tpu.filetest --checker sets \
    "$WL_FIX/sets_valid.edn" >/dev/null
RC_SV=$?
JAX_PLATFORMS=cpu python -m comdb2_tpu.filetest --checker sets \
    "$WL_FIX/sets_lost.edn" >/dev/null
RC_SB=$?
JAX_PLATFORMS=cpu python -m comdb2_tpu.filetest --checker dirty \
    "$WL_FIX/dirty_valid.edn" >/dev/null
RC_DV=$?
JAX_PLATFORMS=cpu python -m comdb2_tpu.filetest --checker dirty \
    "$WL_FIX/dirty_dirty.edn" >/dev/null
RC_DB=$?
set -e
if [ "$RC_BV$RC_SV$RC_DV" != "000" ]; then
    echo "wl clean fixture flagged (bank=$RC_BV sets=$RC_SV" \
         "dirty=$RC_DV)" >&2
    exit 1
fi
if [ "$RC_BB$RC_SB$RC_DB" != "111" ]; then
    echo "wl seeded violation MISSED (bank=$RC_BB sets=$RC_SB" \
         "dirty=$RC_DB)" >&2
    exit 1
fi
run env JAX_PLATFORMS=cpu python scripts/bench_wl.py --quick \
    --json /tmp/bench_wl_smoke.json

# daemon round trip: kind:"wl" rides the same continuous batching
ZOMBIES_BEFORE=$(zombie_count)
WL_LOG=$(mktemp)
JAX_PLATFORMS=cpu python -m comdb2_tpu.service --port 0 \
    --backend cpu --no-prime --frontier 64 >"$WL_LOG" 2>&1 &
WL_PID=$!
CLEANUP_PIDS="$WL_PID"
for _ in $(seq 200); do
    grep -q '"ready"' "$WL_LOG" 2>/dev/null && break
    sleep 0.1
done
grep -q '"ready"' "$WL_LOG" || { echo "wl daemon never ready" >&2; \
    cat "$WL_LOG" >&2; exit 1; }
WL_LOG="$WL_LOG" python - <<'EOF'
import json, os
from comdb2_tpu.checker import wl as W
from comdb2_tpu.ops.history import history_to_edn
from comdb2_tpu.service.client import ServiceClient

port = None
with open(os.environ["WL_LOG"]) as fh:
    for line in fh:
        if '"ready"' in line:
            port = json.loads(line)["port"]
            break
assert port is not None, "no ready line in daemon log"
c = ServiceClient("127.0.0.1", port, timeout_s=300.0, retries=5,
                  backoff_s=0.5)
good, model = W.bank_batch(61, 1)
bad, _ = W.bank_batch(61, 1, violation="total")
r = c.check_wl(history_to_edn(list(good[0])), "bank", wl=model)
assert r["ok"] and r["valid"] is True, r
assert r["kind"] == "wl" and r["family"] == "bank", r
r = c.check_wl(history_to_edn(list(bad[0])), "bank", wl=model)
assert r["ok"] and r["valid"] is False and r["bad-reads"], r
assert r["engine"] == "wl-device", r
assert c.shutdown()
EOF
wait "$WL_PID"
CLEANUP_PIDS=""
rm -f "$WL_LOG"
if pgrep -f "comdb2_tpu\.service" >/dev/null 2>&1; then
    echo "wl daemon left a process behind" >&2
    exit 1
fi
if ! ZOMBIES_AFTER=$(zombies_settled "$ZOMBIES_BEFORE"); then
    echo "wl daemon left a zombie" \
         "($ZOMBIES_BEFORE -> $ZOMBIES_AFTER)" >&2
    exit 1
fi

stage mxu-smoke "MXU frontier engine smoke (wide-P valid + violation)"
# the round-10 engine end to end through the driver ladder: a
# genuinely concurrent P=16 bounded-in-flight history must come back
# VALID and its seeded-violation twin INVALID, BOTH attributed to the
# mxu-frontier engine (wide P is exactly the shape every other engine
# either rejects or answers UNKNOWN on)
run env JAX_PLATFORMS=cpu python - <<'EOF'
import jax

jax.config.update("jax_platforms", "cpu")
from comdb2_tpu.checker import analysis
from comdb2_tpu.models.model import cas_register
from comdb2_tpu.ops import synth_columnar as SC

for violation, want in ((False, True), (True, False)):
    h = SC.wide_register_batch_packed(
        101, 1, n_waves=2, n_chain=13, n_free=3, values=16,
        violation=violation)[0]
    a = analysis(cas_register(), h, backend="device",
                 host_threshold=1)
    assert a.valid is want, (violation, a.valid, a.info)
    assert a.info.get("engine") == "mxu-frontier", a.info
print("mxu smoke: wide-P valid VALID, seeded violation INVALID, "
      "engine=mxu-frontier")
EOF

stage multichip "multichip dryrun (8-device CPU mesh, interpret kernel)"
# the full sharded checking step on the forced 8-device CPU mesh:
# shard_map stream path (fused kernel in interpret mode), kernel/XLA
# bit-parity, escalation on one shard, in-place ladder, wide-P (runs
# in a subprocess so the corrected env lands before any jax import)
run env JAX_PLATFORMS=cpu python -c \
    "import __graft_entry__ as g; g.dryrun_multichip(8)"

stage service-smoke "verifier service smoke (CPU backend)"
# zombie baseline BEFORE the daemon runs: the post-shutdown check
# below must catch NEW zombies (a reaped child can't show Z, so the
# meaningful assertion is "no more Z states than before, and no
# surviving service process")
ZOMBIES_BEFORE=$(zombie_count)
SVC_LOG=$(mktemp)
JAX_PLATFORMS=cpu python -m comdb2_tpu.service --port 0 \
    --backend cpu --no-prime --frontier 64 >"$SVC_LOG" 2>&1 &
SVC_PID=$!
CLEANUP_PIDS="$SVC_PID"
for _ in $(seq 200); do     # the ready line carries the chosen port
    grep -q '"ready"' "$SVC_LOG" 2>/dev/null && break
    sleep 0.1
done
grep -q '"ready"' "$SVC_LOG" || { echo "daemon never became ready" >&2; \
    cat "$SVC_LOG" >&2; exit 1; }
SVC_LOG="$SVC_LOG" python - <<'EOF'
import json, os
from comdb2_tpu.ops import op as O
from comdb2_tpu.service.client import ServiceClient

# the log merges stdout+stderr, and jax/absl init noise may precede
# the ready line — scan for it instead of assuming line 1
port = None
with open(os.environ["SVC_LOG"]) as fh:
    for line in fh:
        if '"ready"' in line:
            port = json.loads(line)["port"]
            break
assert port is not None, "no ready line in daemon log"
c = ServiceClient("127.0.0.1", port, timeout_s=300.0, retries=5,
                  backoff_s=0.5)
h = [O.invoke(0, "write", 1), O.ok(0, "write", 1),
     O.invoke(1, "read", None), O.Op(1, "ok", "read", 1)]
r = c.check(h)
assert r.get("ok") and r.get("valid") is True, r
st = c.status()["status"]
assert st["completed"] >= 1 and st["dispatches"] >= 1, st
assert c.shutdown()
EOF
wait "$SVC_PID"            # clean exit 0, and the wait reaps it
CLEANUP_PIDS=""
# the daemon itself is reaped by the wait above — what must NOT
# remain is any surviving service process or a NEW zombie it left
# behind (ps -o stat= per CLAUDE.md: pkill'd daemons linger as Z)
if pgrep -f "comdb2_tpu\.service" >/dev/null 2>&1; then
    echo "verifier daemon left a process behind" >&2
    exit 1
fi
if ! ZOMBIES_AFTER=$(zombies_settled "$ZOMBIES_BEFORE"); then
    echo "verifier daemon left a zombie" \
         "($ZOMBIES_BEFORE -> $ZOMBIES_AFTER)" >&2
    exit 1
fi

stage stream "streaming verification sessions smoke (kind:\"stream\")"
# the live-history path end to end (docs/streaming.md): open a
# session, append a clean delta (valid-so-far), append a violating
# delta (INVALID latches — later appends answer immediately), two
# concurrent sessions sharing ONE megabatched dispatch, close,
# clean shutdown, no zombies. --fill-ms 50 widens the coalescing
# window so the concurrent appends deterministically share a beat
ZOMBIES_BEFORE=$(zombie_count)
STRM_LOG=$(mktemp)
JAX_PLATFORMS=cpu python -m comdb2_tpu.service --port 0 \
    --backend cpu --no-prime --frontier 256 \
    --max-sessions 4 --fill-ms 50 >"$STRM_LOG" 2>&1 &
STRM_PID=$!
CLEANUP_PIDS="$STRM_PID"
for _ in $(seq 200); do
    grep -q '"ready"' "$STRM_LOG" 2>/dev/null && break
    sleep 0.1
done
grep -q '"ready"' "$STRM_LOG" || { echo "stream daemon never became ready" >&2; \
    cat "$STRM_LOG" >&2; exit 1; }
STRM_LOG="$STRM_LOG" python - <<'EOF'
import json, os
from comdb2_tpu.ops import op as O
from comdb2_tpu.ops.history import history_to_edn
from comdb2_tpu.service.client import ServiceClient

port = None
with open(os.environ["STRM_LOG"]) as fh:
    for line in fh:
        if '"ready"' in line:
            port = json.loads(line)["port"]
            break
assert port is not None, "no ready line in daemon log"
c = ServiceClient("127.0.0.1", port, timeout_s=300.0, retries=5,
                  backoff_s=0.5)
r = c.stream_open()
assert r.get("ok") and r.get("session"), r
sid = r["session"]
clean = [O.invoke(0, "write", 1), O.ok(0, "write", 1),
         O.invoke(1, "read", None), O.Op(1, "ok", "read", 1)]
r = c.stream_append(sid, history_to_edn(clean))
assert r.get("ok") and r.get("valid") is True, r
assert r.get("checked_through") == 4, r
bad = [O.invoke(1, "read", None), O.Op(1, "ok", "read", 9)]
r = c.stream_append(sid, history_to_edn(bad))
assert r.get("ok") and r.get("valid") is False, r
# the latch: a third append answers immediately, no device work
r = c.stream_append(sid, history_to_edn(clean))
assert r.get("ok") and r.get("valid") is False and r.get("latched"), r
r = c.stream_close(sid)
assert r.get("ok") and r.get("valid") is False, r
# megabatched advance (docs/streaming.md "Megabatched advance"): two
# sessions appending in one beat share ONE launched program — the
# barrier puts both requests inside the daemon's coalescing window
import threading
ca = ServiceClient("127.0.0.1", port, timeout_s=300.0, retries=5,
                   backoff_s=0.5)
cb = ServiceClient("127.0.0.1", port, timeout_s=300.0, retries=5,
                   backoff_s=0.5)
sa = ca.stream_open()["session"]
sb = cb.stream_open()["session"]
fused = False
for attempt in range(3):
    mb0 = c.status()["status"]["stream_megabatches"]
    delta = [O.invoke(0, "write", attempt), O.ok(0, "write", attempt),
             O.invoke(1, "read", None), O.Op(1, "ok", "read", attempt)]
    bar = threading.Barrier(2)
    res = {}
    def go(cli, sid, key):
        bar.wait()
        res[key] = cli.stream_append(sid, history_to_edn(delta))
    ts = [threading.Thread(target=go, args=(ca, sa, "a")),
          threading.Thread(target=go, args=(cb, sb, "b"))]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert res["a"].get("valid") is True, res
    assert res["b"].get("valid") is True, res
    if c.status()["status"]["stream_megabatches"] > mb0:
        fused = True
        break
assert fused, "concurrent same-class appends never shared a dispatch"
ca.stream_close(sa); cb.stream_close(sb)
ca.close(); cb.close()
st = c.status()["status"]
assert st["stream_opens"] >= 3 and st["stream_appends"] >= 5, st
assert st["stream"]["sessions"] == 0, st
m = c.metrics()
assert "stream_sessions_active" in m["prometheus"]
assert "sessions_per_dispatch" in m["prometheus"]
assert c.shutdown()
EOF
wait "$STRM_PID"
CLEANUP_PIDS=""
if pgrep -f "comdb2_tpu\.service" >/dev/null 2>&1; then
    echo "stream daemon left a process behind" >&2
    exit 1
fi
if ! ZOMBIES_AFTER=$(zombies_settled "$ZOMBIES_BEFORE"); then
    echo "stream daemon left a zombie" \
         "($ZOMBIES_BEFORE -> $ZOMBIES_AFTER)" >&2
    exit 1
fi
run python scripts/bench_stream.py --quick --json /tmp/bench_stream_smoke.json

stage routing "pmux-routed two-daemon fleet smoke"
# the horizontal-scale path end to end: two daemons register under
# ct_pmux (sut/verifier/0, sut/verifier/1), the consistent-hash
# client discovers them and routes 8 mixed-shape requests — BOTH
# daemons must serve traffic, and everything must shut down clean
# with no zombies (docs/service.md "Horizontal scale-out")
ZOMBIES_BEFORE=$(zombie_count)
RT_PMUX_PORT=${CT_CHECK_ROUTING_PMUX_PORT:-15106}
ASAN_OPTIONS=halt_on_error=1 "$PMUX" -p "$RT_PMUX_PORT" &
RT_PMUX_PID=$!
RT_LOG0=$(mktemp); RT_LOG1=$(mktemp)
CLEANUP_PIDS="$RT_PMUX_PID"
for _ in $(seq 50); do
    if bash -c "true >/dev/tcp/127.0.0.1/$RT_PMUX_PORT" 2>/dev/null; then
        break
    fi
    sleep 0.1
done
JAX_PLATFORMS=cpu python -m comdb2_tpu.service --port 0 \
    --backend cpu --no-prime --frontier 64 \
    --pmux "$RT_PMUX_PORT" --pmux-shard 0 >"$RT_LOG0" 2>&1 &
RT_PID0=$!
CLEANUP_PIDS="$RT_PMUX_PID $RT_PID0"
JAX_PLATFORMS=cpu python -m comdb2_tpu.service --port 0 \
    --backend cpu --no-prime --frontier 64 \
    --pmux "$RT_PMUX_PORT" --pmux-shard 1 >"$RT_LOG1" 2>&1 &
RT_PID1=$!
CLEANUP_PIDS="$RT_PMUX_PID $RT_PID0 $RT_PID1"
for LOG in "$RT_LOG0" "$RT_LOG1"; do
    for _ in $(seq 200); do
        grep -q '"ready"' "$LOG" 2>/dev/null && break
        sleep 0.1
    done
    grep -q '"ready"' "$LOG" || { echo "routing daemon never ready" >&2; \
        cat "$LOG" >&2; exit 1; }
done
RT_PMUX_PORT="$RT_PMUX_PORT" python - <<'EOF'
import os, random
from comdb2_tpu.ops.history import history_to_edn
from comdb2_tpu.ops.synth import register_history
from comdb2_tpu.service.client import RoutedClient

rc = RoutedClient.discover(pmux_port=int(os.environ["RT_PMUX_PORT"]),
                           timeout_s=300.0, retries=5, backoff_s=0.5)
assert set(rc.clients) == {"sut/verifier/0", "sut/verifier/1"}, \
    sorted(rc.clients)
# 8 requests across enough size classes that the shape-class ring
# provably touches both daemons (class->daemon is deterministic md5)
for i, n_events in enumerate((10, 18, 30, 60, 10, 18, 30, 60)):
    h = register_history(random.Random(100 + i), 3, n_events,
                         p_info=0.0)
    r = rc.check(history_to_edn(h))
    assert r.get("ok") and r.get("valid") is True, r
assert all(v > 0 for v in rc.served.values()), \
    f"a daemon served nothing: {rc.served}"
sts = rc.statuses()
assert len(sts) == 2 and \
    all(st["completed"] >= 1 for st in sts.values()), sts
for c in rc.clients.values():
    assert c.shutdown()
EOF
wait "$RT_PID0"
wait "$RT_PID1"
exec 3<>"/dev/tcp/127.0.0.1/$RT_PMUX_PORT"
printf 'exit\n' >&3
cat <&3 >/dev/null || true
exec 3<&- 3>&-
wait "$RT_PMUX_PID"
CLEANUP_PIDS=""
rm -f "$RT_LOG0" "$RT_LOG1"
if pgrep -f "comdb2_tpu\.service" >/dev/null 2>&1; then
    echo "routing smoke left a daemon behind" >&2
    exit 1
fi
if ! ZOMBIES_AFTER=$(zombies_settled "$ZOMBIES_BEFORE"); then
    echo "routing smoke left a zombie" \
         "($ZOMBIES_BEFORE -> $ZOMBIES_AFTER)" >&2
    exit 1
fi

stage elastic "elastic fleet smoke (supervisor, SIGKILL nemesis, join, migration)"
# the round-12 gate, quick form: the supervisor boots a 2-daemon
# pmux-registered fleet, one daemon is SIGKILLed mid-traffic (the
# survivor must serve its remapped classes; the supervisor reaps the
# corpse, deletes its stale registration, bumps the ring epoch and
# respawns to the floor), a third daemon joins under burst (~1/N
# shape-class remap, gated), a streaming session migrates off a
# draining daemon by checkpoint (O(delta) afterward — no replay),
# and the client-observed fleet history is checked VALID by the
# fleet itself. Zombie accounting shell-side too: the supervisor
# must reap every child (no init reaper in this container).
ZOMBIES_BEFORE=$(zombie_count)
run env JAX_PLATFORMS=cpu python scripts/bench_elastic.py --quick \
    --out /tmp/bench_elastic_smoke.json
if pgrep -f "comdb2_tpu\.service" >/dev/null 2>&1; then
    echo "elastic smoke left a daemon behind" >&2
    exit 1
fi
if ! ZOMBIES_AFTER=$(zombies_settled "$ZOMBIES_BEFORE"); then
    echo "elastic smoke left a zombie" \
         "($ZOMBIES_BEFORE -> $ZOMBIES_AFTER)" >&2
    exit 1
fi

stage obs "tracing + metrics plane smoke (daemon --trace --store)"
# boot with tracing on, run one check + one shrink, scrape the
# metrics (kind:"metrics"), then assert the shutdown trace artifact
# is non-empty valid Perfetto JSON and the scrape carried nonzero
# dispatch + queue-wait histograms (docs/observability.md)
OBS_STORE=$(mktemp -d)
OBS_LOG=$(mktemp)
JAX_PLATFORMS=cpu python -m comdb2_tpu.service --port 0 \
    --backend cpu --no-prime --frontier 64 --trace \
    --store "$OBS_STORE" >"$OBS_LOG" 2>&1 &
OBS_PID=$!
CLEANUP_PIDS="$OBS_PID"
for _ in $(seq 200); do
    grep -q '"ready"' "$OBS_LOG" 2>/dev/null && break
    sleep 0.1
done
grep -q '"ready"' "$OBS_LOG" || { echo "obs daemon never ready" >&2; \
    cat "$OBS_LOG" >&2; exit 1; }
OBS_LOG="$OBS_LOG" python - <<'EOF'
import json, os
from comdb2_tpu.ops import op as O
from comdb2_tpu.service.client import ServiceClient

port = None
with open(os.environ["OBS_LOG"]) as fh:
    for line in fh:
        if '"ready"' in line:
            port = json.loads(line)["port"]
            break
assert port is not None, "no ready line in daemon log"
c = ServiceClient("127.0.0.1", port, timeout_s=300.0, retries=5,
                  backoff_s=0.5)
bad = [O.invoke(0, "write", 1), O.ok(0, "write", 1),
       O.invoke(1, "read", None), O.Op(1, "ok", "read", 2)]
r = c.check(bad)
assert r["ok"] and r["valid"] is False, r
assert r.get("stages"), r            # the per-stage attribution
r = c.shrink(bad)
assert r["ok"] and r["valid"] is False, r
m = c.metrics()
assert m["ok"] and m["kind"] == "metrics", m
snap = m["metrics"]
qw = sum(s["count"] for s in snap["service_queue_wait_ms"]["series"])
dev = sum(s["count"] for s in snap["service_device_ms"]["series"])
assert qw > 0 and dev > 0, (qw, dev)
assert snap["service_dispatches_total"]["series"][0]["value"] > 0
assert "service_queue_wait_ms_bucket" in m["prometheus"]
assert c.shutdown()
EOF
wait "$OBS_PID"
CLEANUP_PIDS=""
TRACE="$OBS_STORE/service/trace.json"
[ -s "$TRACE" ] || { echo "obs daemon wrote no trace artifact" >&2; \
    exit 1; }
python - "$TRACE" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
ev = doc["traceEvents"]
assert ev, "trace artifact is empty"
names = {e["name"] for e in ev}
assert {"admission", "device", "request"} <= names, names
EOF
[ -s "$OBS_STORE/service/timeline.svg" ] || \
    { echo "obs daemon wrote no timeline.svg" >&2; exit 1; }
rm -rf "$OBS_STORE" "$OBS_LOG"

stage_end_ok
if [ "$JSON_MODE" = 0 ]; then
    echo "OK: checker clean, ASan build clean, native static" \
         "analysis clean, ct_pmux shutdown clean under ASan and TSan" \
         "(8 concurrent clients), txn smoke caught" \
         "the seeded cycle, shrink smoke reached the known minimum," \
         "wl smoke caught every seeded family violation with" \
         "device/oracle parity and a clean daemon round trip," \
         "mxu smoke answered both wide-P fixtures," \
         "multichip dryrun bit-identical across the mesh," \
         "verifier service shutdown clean, two-daemon pmux routing" \
         "served on both shards, elastic smoke survived the SIGKILL" \
         "nemesis + join + checkpoint migration with the fleet" \
         "history VALID, obs smoke traced a check+shrink" \
         "with populated histograms"
fi
