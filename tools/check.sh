#!/usr/bin/env bash
# One-stop pre-merge gate: rt-lint (static invariants) then the tier-1 test
# suite (ROADMAP.md "Tier-1 verify"). Usage: tools/check.sh [--lint-only]
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== rt-lint (ray_tpu.devtools) =="
python -m ray_tpu.devtools.lint ray_tpu

echo
echo "== rt-verify (session machine + lock order + native C + stale binaries) =="
python -m ray_tpu.devtools.verify ray_tpu

if [[ "${1:-}" == "--lint-only" ]]; then
    exit 0
fi

echo
echo "== rt-verify explore (control-plane interleaving sweep + corpus replay) =="
timeout -k 10 180 env JAX_PLATFORMS=cpu \
    python -m ray_tpu.devtools.verify ray_tpu --passes stale --explore all

echo
echo "== native wire-codec parity fuzz (from-source build + C/py byte parity) =="
timeout -k 10 180 env JAX_PLATFORMS=cpu python tools/native_parity_fuzz.py

echo
echo "== wire decoder fuzz (structure-aware mutations, corpus replay, >=10k/codec) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m ray_tpu.devtools.verify ray_tpu --passes none --fuzz 12000

echo
echo "== sanitizer replay (ASan/UBSan rebuild + fuzz corpus + arena stress) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/sanitize_native.py

echo
echo "== chaos smoke (seeded failpoint schedule) =="
timeout -k 10 180 env JAX_PLATFORMS=cpu python tools/chaos_smoke.py

echo
echo "== introspection smoke (stacks + memory + profile on a mini-cluster) =="
timeout -k 10 120 env JAX_PLATFORMS=cpu python tools/introspect_smoke.py

echo
echo "== data-plane smoke (peer-direct transfers, zero head relay) =="
timeout -k 10 120 env JAX_PLATFORMS=cpu python tools/dataplane_smoke.py

echo
echo "== serve ingress smoke (2-proxy fleet, burst->shed->recover, drain-on-stop) =="
timeout -k 10 180 env JAX_PLATFORMS=cpu python tools/serve_smoke.py

echo
echo "== observability smoke (series history, event log, shed alert fire->resolve) =="
timeout -k 10 180 env JAX_PLATFORMS=cpu python tools/obs_smoke.py

echo
echo "== jobs smoke (2-driver mini-cluster: attribution + job_starved fire->resolve) =="
timeout -k 10 180 env JAX_PLATFORMS=cpu python tools/jobs_smoke.py

echo
echo "== trace smoke (one Serve request traced proxy->router->replica->task, latency report) =="
timeout -k 10 180 env JAX_PLATFORMS=cpu python tools/trace_smoke.py

echo
echo "== train smoke (4-worker gang, seeded straggler named + alert fire->resolve, goodput ledger) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python tools/train_smoke.py

echo
echo "== elastic smoke (4-worker gang, seeded kill -> resize-in-place at world 3, bit-exact resume) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python tools/elastic_smoke.py

echo
echo "== v5e ahead-of-time compile (no chip: gpt2_small step on 1 and 4 devices fits HBM, kernels in it) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest tests/test_aot_v5e.py -q -m slow \
    -p no:cacheprovider

echo
echo "== chip smoke rehearsal (every phase of chip_smoke.py at toy size on CPU; not a chip result) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python chip_smoke.py --rehearse-cpu

echo
echo "== tier-1 tests =="
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
