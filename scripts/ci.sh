#!/usr/bin/env bash
# One-command verify recipe: tier-1 tests + the VMEM-tiled kernel
# smoke (DESIGN.md §10: tiled selection/exchange in interpret mode at
# shapes whose one-shot working set exceeds the VMEM budget) + a
# reduced-scale run of the attack-resilience example (the in-graph
# ThreatModel path end-to-end, attacks firing inside a gossip
# segment) + the §11 ANN selection smoke (sub-quadratic candidate
# path at M=16384 — beyond the exact kernels' comfortable range —
# plus recall and the one-bucket bit-exact fallback) + the §13 continuous-service smoke (3 churned
# reselection periods, kill after 2, bit-exact resume + ledger
# verification across the restart, batched personalized serving)
# + the §15 chaos soak (every fault kind of a seeded FaultPlan firing
# against the hardened transport: degraded rounds within tolerance of
# fault-free, crash + truncated snapshot + forked ledger recovered
# bitwise, identical fault traces for the same seed)
# + a 1024-client dryrun on the tiled backend
# (the 10^4-client scaling path lowered under sharding, in a fresh
# process because jax locks the device count at first init).
# The static-analysis gate (DESIGN.md §12/§14) runs FIRST: kernel
# contracts + trace lint + the privacy-taint verifier are cheap (no
# kernel executes) and catch the §10/§4 bug classes — and any
# disclosure-boundary leak — before the test tiers spend minutes. The
# gate's wall-time is recorded in benchmarks/ANALYSIS_report.json. The
# seeded-leak fixtures are then each asserted to FAIL the strict gate:
# a verifier that stops flagging planted leaks is itself broken.
# Everything here is a CPU tool: it runs under JAX_PLATFORMS=cpu, with
# the Pallas kernels in interpret mode, on a machine with a TPU too (a
# chip belongs to one process; the chip run is `python chip_smoke.py`).
# Usage: scripts/ci.sh [extra pytest args]
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS=cpu

echo "== static analysis: contracts + lint + privacy taint (strict) =="
python -m repro.analysis --strict --json benchmarks/ANALYSIS_report.json

echo "== seeded-leak fixtures must fail the strict gate =="
for leak in tests/analysis_fixtures/leak_announce_field.py \
            tests/analysis_fixtures/leak_metric_tap.py \
            tests/analysis_fixtures/leak_served_private.py; do
    if python -m repro.analysis --strict "$leak" >/dev/null 2>&1; then
        echo "FATAL: $leak passed the strict gate (planted leak missed)"
        exit 1
    fi
    echo "ok: $leak rejected"
done

echo "== tier-1: pytest =="
python -m pytest -x -q "$@"

echo "== tiled kernels beyond the one-shot VMEM budget (smoke) =="
python scripts/tiled_smoke.py

echo "== sub-quadratic ANN selection smoke (DESIGN.md §11) =="
python scripts/ann_smoke.py

echo "== continuous federation service: churn + kill/resume (DESIGN.md §13) =="
python scripts/service_smoke.py

echo "== chaos soak: faults + degraded mode + crash/fork recovery (DESIGN.md §15) =="
python scripts/chaos_smoke.py

echo "== attack-resilience example (smoke) =="
python examples/attack_resilience.py --clients 6 --rounds 3 \
    --per-client 48 --reselect-every 3

echo "== 1024-client dryrun on the tiled backend =="
XLA_FLAGS="--xla_force_host_platform_device_count=512" \
    python -m repro.launch.fed --dryrun --clients 1024 \
    --ref-mode public --tiling tiled

echo "CI OK"
