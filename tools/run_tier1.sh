#!/usr/bin/env bash
# Canonical tier-1 verification: the pytest invocation the driver runs
# (6 xdist workers, --dist loadfile, 1470 s wall), behind the native
# warm-up and the vmqlint gate, so CI and humans run the same thing.
#
#   tools/run_tier1.sh                 # tier-1: everything but -m slow
#   tools/run_tier1.sh -m chaos        # your -m replaces the marker filter
#   tools/run_tier1.sh -k spool -x     # other args pass through, tier-1
#                                      # marker filter kept
#
# Exits with pytest's status; prints DOTS_PASSED=<n> for the driver.
# Chaos/soak tests are opt-in: they carry BOTH the `chaos` and `slow`
# markers, so tier-1's `-m 'not slow'` excludes them (run them with
# `tools/run_tier1.sh -m chaos`, or set TIER1_CHAOS=1 to append the
# chaos leg after a green tier-1 run).
set -o pipefail
cd "$(dirname "$0")/.."

LOG=${TIER1_LOG:-/tmp/_t1.log}
TIMEOUT=${TIER1_TIMEOUT:-1470}
WORKERS=${TIER1_WORKERS:-6}
if [ $# -gt 0 ]; then
  case " $* " in
    *" -m "*|*" -m="*|*" --markers "*) EXTRA=("$@") ;;
    *) EXTRA=(-m 'not slow' "$@") ;;
  esac
else
  EXTRA=(-m 'not slow')
fi

# best-effort native build (wire codec + kvstore/counters/fence): the
# loaders build on demand anyway, but warming here keeps the first
# test that touches the codec from paying the compile inside its own
# timeout. Skips cleanly when no toolchain is present — every native
# consumer has a bit-identical pure-Python fallback.
if command -v g++ >/dev/null 2>&1 || command -v c++ >/dev/null 2>&1; then
  make -C native >/dev/null 2>&1 || true
fi

# loaded-codec version assertion: when the warmup produced a wire-codec
# extension, its baked-in FASTPATH_VERSION must match the source header
# — a stale .so served from the build cache would otherwise shadow a
# contract bump and every "native" test result would be a lie. The
# runtime loader enforces min_version too; this catches it BEFORE 700
# tests run against the wrong module. (Skips cleanly when the codec
# didn't build: the pure twin is the contract then.)
python - <<'PYEOF' || exit 1
import re, sys
from vernemq_tpu.protocol import fastpath

mod = fastpath.load_native()
if mod is not None:
    src = open("native/codec.cc", encoding="utf-8").read()
    m = re.search(r"FASTPATH_VERSION\s*=\s*(\d+)", src)
    want = int(m.group(1))
    got = getattr(mod, "FASTPATH_VERSION", None)
    if got != want or want != fastpath.REQUIRED_VERSION:
        sys.exit(f"stale wire codec: loaded FASTPATH_VERSION={got}, "
                 f"source header says {want}, loader requires "
                 f"{fastpath.REQUIRED_VERSION} — rebuild native/")
PYEOF

# pre-test static gate: the unified vmqlint suite (tools/vmqlint) —
# blocking calls in async bodies, metric-registry HELP/observe names,
# lock discipline (no device/compile/IO under a threading lock),
# thread lifecycle (every started thread joined/cancelled from close),
# knob registry (config reads <-> DEFAULTS <-> schema aliases agree),
# fault-point/breaker-path registry (inject sites and admin drills
# can't drift). A regression in any defect class fails tier-1 before a
# single test runs. Fast local iteration: `python -m tools.vmqlint
# --changed` scopes the file-level passes to the git working-set.
python -m tools.vmqlint || exit 1

# hung-test forensics: faulthandler dumps every thread's stack just
# below the outer timeout wall (tests/conftest.py arms it), so a wedged
# test prints WHERE it hung instead of dying silently at the kill.
# Short walls keep a small margin so the dump still beats the SIGTERM;
# non-positive disables (conftest skips arming).
DUMP_S=${TIER1_FAULTHANDLER_S:-$((TIMEOUT > 60 ? TIMEOUT - 30 : TIMEOUT - 5))}

rm -f "$LOG"
timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu \
  TIER1_FAULTHANDLER_S="$DUMP_S" \
  python -m pytest tests/ -q "${EXTRA[@]}" \
  --continue-on-collection-errors -p no:cacheprovider -p xdist \
  -n "$WORKERS" --dist loadfile -p no:randomly 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}

# opt-in chaos leg (TIER1_CHAOS=1): after a green tier-1 run, also run
# the fault-injection soaks (`-m chaos` — partition storms, handoff
# bounce, filter/watchdog chaos). Kept out of the default gate because
# the soaks are long; CI jobs that want the full robustness sweep set
# the env var instead of remembering a second command.
if [ "${TIER1_CHAOS:-0}" = "1" ] && [ "$rc" -eq 0 ]; then
  CLOG=${TIER1_CHAOS_LOG:-/tmp/_t1_chaos.log}
  rm -f "$CLOG"
  timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu \
    TIER1_FAULTHANDLER_S="$DUMP_S" \
    python -m pytest tests/ -q -m chaos \
    --continue-on-collection-errors -p no:cacheprovider -p xdist \
    -n "$WORKERS" --dist loadfile -p no:randomly 2>&1 | tee "$CLOG"
  rc=${PIPESTATUS[0]}
  cat "$CLOG" >> "$LOG"
fi

echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" | tr -cd . | wc -c)"
exit "$rc"
