#!/usr/bin/env bash
# Minimal CI: tier-1 suite on CPU with Pallas kernels in interpret mode,
# plus example smoke runs so API breakage in examples fails CI.
#
# Off-TPU every pallas_call auto-selects interpret=True (see
# repro.kernels.interpret_default), so this exercises the real kernel
# dataflow — including the fused exit-gate chain — without hardware.
#
#   ./scripts/ci.sh            # whole tier-1 suite + example smoke
#   ./scripts/ci.sh tests/test_exit_gate.py   # one file (skips examples)
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m pytest -x -q "$@"

# example smoke (tiny configs, interpret mode): quickstart drives
# Engine/DecodeSession directly, serve_specee trains a minimal bundle and
# serves through the continuous-batching engine. Only on full-suite runs.
if [ "$#" -eq 0 ]; then
  echo "[ci] examples/quickstart.py (smoke)"
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python examples/quickstart.py --new-tokens 3
  echo "[ci] examples/serve_specee.py --ci (smoke)"
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python examples/serve_specee.py --ci
  # paged-cache serving smoke: exercises the KVCacheManager page-table path
  # and the chunked-prefill scheduler on every run (page leak + budget
  # asserts live behind --ci)
  echo "[ci] launch/serve.py --ci --page-size 16 (paged smoke)"
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.launch.serve --ci --page-size 16
  # megatick serving smoke: device-resident K-tick decode + async pipeline.
  # --ci with --megatick > 1 asserts completion, zero page leak, and token
  # parity against a megatick=1 reference run internally.
  echo "[ci] launch/serve.py --ci --megatick 8 (megatick smoke)"
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.launch.serve --ci --megatick 8
  # quantized serving smoke: weight-only int8 bundle (repro.quant) with
  # dequant fused into the decode kernels; --ci asserts completion, zero
  # page leak, and token parity against a quantized megatick=1 reference
  echo "[ci] launch/serve.py --ci --quant int8 --megatick 4 (quant smoke)"
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.launch.serve --ci --quant int8 --megatick 4

  # kill/restore smoke: SIGTERM a serving run mid-decode (the engine drains
  # the in-flight megatick, saves a step-atomic checkpoint, exits 17), then
  # restart with --restore — --ci asserts the resumed run completes every
  # request token-identical to a fault-free reference with zero page leak.
  # If the run wins the race and finishes before the signal (rc 0), the
  # restore run finds an empty checkpoint dir and serves fresh — the same
  # asserts still hold.
  echo "[ci] launch/serve.py kill/restore smoke (SIGTERM mid-decode)"
  CKPT_DIR="$(mktemp -d)"
  trap 'rm -rf "$CKPT_DIR"' EXIT
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.launch.serve --ci --megatick 4 \
      --checkpoint-dir "$CKPT_DIR" >/dev/null 2>&1 &
  SERVE_PID=$!
  sleep 8
  kill -TERM "$SERVE_PID" 2>/dev/null || true
  RC=0; wait "$SERVE_PID" || RC=$?
  if [ "$RC" -ne 17 ] && [ "$RC" -ne 0 ]; then
    echo "[ci] kill/restore smoke: serve exited rc=$RC (want 17 or 0)" >&2
    exit 1
  fi
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.launch.serve --ci --megatick 4 \
      --checkpoint-dir "$CKPT_DIR" --restore

  # fault-injection sweep: each named site fires once (deterministic
  # schedule); --ci asserts every request still completes token-identical
  # to the fault-free reference and the pool leaks nothing. The sigterm
  # site preempts + restores in-process.
  for SITE in dispatch finish_timeout nan_logits pool_exhausted sigterm; do
    echo "[ci] launch/serve.py --ci --inject $SITE (fault-injection sweep)"
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
      python -m repro.launch.serve --ci --megatick 4 --inject "$SITE"
  done

  # elastic remesh smoke (DESIGN.md §10): lose a device out of a TP=2 mesh
  # mid-decode — the engine must remesh to TP=1 in place (not die), finish
  # every request token-identical to the unsharded fault-free reference,
  # and export a non-empty JSONL fault trail.
  echo "[ci] launch/serve.py --ci --inject device_lost --mesh 1,2 (remesh smoke)"
  FLOG="$(mktemp)"
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.launch.serve --ci --megatick 4 --mesh 1,2 \
      --inject device_lost --fault-log "$FLOG"
  if ! grep -q '"action": "remesh"' "$FLOG"; then
    echo "[ci] remesh smoke: no remesh event in fault log $FLOG" >&2
    exit 1
  fi
  rm -f "$FLOG"

  # sharded serving smoke (DESIGN.md §9): tensor-parallel megatick on forced
  # host devices — --ci asserts token parity against an unsharded reference
  # run in the same process; then a 2-replica data-parallel pool whose
  # outputs must match a single-engine run.
  echo "[ci] launch/serve.py --ci --mesh 1,2 --megatick 4 (TP smoke)"
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.launch.serve --ci --mesh 1,2 --megatick 4
  echo "[ci] launch/serve.py --ci --replicas 2 (replica-pool smoke)"
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.launch.serve --ci --replicas 2

  # exit-gate perf gate (ROADMAP item 5): re-measure the fused gate and the
  # quantized streaming verify against the committed BENCH_exit_gate.json
  # row groups; quant_pareto quality (match_vs_dense_fp32 == 1.0) is
  # checked statically. The interpret-mode Pallas column is never re-timed.
  echo "[ci] bench_predictor --gate (exit-gate regression gate)"
  PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.bench_predictor --gate
fi
