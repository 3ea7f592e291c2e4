#!/bin/bash
# Self-check mirroring what the round driver/judge runs, CPU-only
# (JAX_PLATFORMS=cpu for every step; the chip check is chip_smoke.py).
# Usage: bash tools/roundcheck.sh [--full]
#   default: suite + dryruns + fast parity (heart)      (~12 min)
#   --full:  adds the full parity config set            (~30+ min)
set -u
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
fail=0
step() { echo; echo "=== $1 ==="; }

step "pytest (8-virtual-device CPU mesh)"
python -m pytest tests/ -q || fail=1

step "dryrun_multichip(8)"
python -c "
import __graft_entry__ as g; g.dryrun_multichip(8)" || fail=1

step "dryrun_multihost(2)"
python -c "
import __graft_entry__ as g; g.dryrun_multihost(2)" || fail=1

step "entry() compile check"
python -c "
import jax
import __graft_entry__ as g
fn, args = g.entry()
out = jax.jit(fn)(*args); jax.block_until_ready(out); print('entry OK')" || fail=1

if [ "${1:-}" = "--full" ]; then
  step "parity (all configs, f64)"
  python tools/parity.py || fail=1
else
  step "parity smoke (heart, f64)"
  python tools/parity.py --fast --configs heart || fail=1
  rm -f PARITY.md.partial
fi

step "bench smoke (CPU)"
PHOTON_ML_TPU_BENCH_CPU=1 python bench.py > /tmp/bench_smoke.json 2>/dev/null \
  && python -c "
import json; d = json.load(open('/tmp/bench_smoke.json'))
assert d['value'] > 0, d
print('bench OK:', d['metric'], d['value'])" || fail=1

echo
[ $fail -eq 0 ] && echo "ROUNDCHECK: ALL OK" || echo "ROUNDCHECK: FAILURES (see above)"
exit $fail
