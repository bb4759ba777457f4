#!/usr/bin/env bash
# CI smoke for paddle_tpu (paddle/scripts/paddle_build.sh role, compact):
#   1. full test suite on the virtual-CPU mesh
#   2. quick per-op micro-benchmarks, compared against the committed
#      OP_BENCH.json baseline (>2x step-time regressions fail the run
#      only with CI_STRICT_PERF=1; they always print)
#   3. bench.py CPU dry-run of the CTR config (exercises the native PS)
#   4. chip_smoke.py's CPU rehearsal: the chip run's control flow at a
#      tiny size, kernels interpreted (the chip itself is reached with
#      `python chip_smoke.py` on a machine that has one)
# Usage: scripts/ci.sh [pytest-args...]
set -u -o pipefail
cd "$(dirname "$0")/.."

rc=0
out=_scratch/ci   # gitignored; nothing is written outside the checkout
mkdir -p "$out"

echo "== [1/4] pytest =="
python -m pytest tests/ -q -x "$@" || rc=1

echo "== [1b] README bench-claim hygiene =="
python tools/check_readme_bench.py || rc=1

echo "== [1c] static analyzer gate (AST lints + cached program analyses) =="
if python tools/static_check.py --fast --json > $out/static_check.json; then
  echo "static-check: pass (see $out/static_check.json)"
else
  echo "static-check: NEW findings (see $out/static_check.json; fix or justify in ANALYSIS_BASELINE.json)"
  rc=1
fi

echo "== [2/4] op micro-bench (quick, vs baseline) =="
if python tools/op_bench.py --cpu --quick --compare; then
  echo "op-bench: no >2x regressions"
else
  echo "op-bench: regressions detected (see above)"
  if [ "${CI_STRICT_PERF:-0}" = "1" ]; then rc=1; fi
fi

echo "== [2b] perf gate (quick 2-row smoke vs committed baselines) =="
if python tools/perf_gate.py --cpu --quick --out $out/PERF_GATE.json; then
  echo "perf-gate: pass (see $out/PERF_GATE.json)"
else
  echo "perf-gate: regressions/missing rows detected (see above)"
  rc=1
fi

echo "== [2c] kernel autotune smoke sweep (dry-run, mechanics only) =="
if python tools/autotune.py --cpu --smoke --dry-run > $out/autotune_smoke.json; then
  echo "autotune: smoke sweep ok (see $out/autotune_smoke.json)"
else
  echo "autotune: smoke sweep FAILED"
  rc=1
fi

echo "== [3/4] bench dry-run (ctr_ps, small, cpu) =="
JAX_PLATFORMS=cpu python - <<'PY' || rc=1
import _cpu_debug  # noqa: F401
import bench

r = bench._ctr_dnn_ps(batch=256, chunks=2, merge_k=2)
assert "value" in r, r
print("ctr dry-run ok:", r["value"], r["unit"])
PY

echo "== [4/4] chip_smoke.py rehearsal (cpu, tiny, interpreted kernels) =="
JAX_PLATFORMS=cpu python chip_smoke.py --rehearse || rc=1

exit $rc
