#!/usr/bin/env bash
# TPU resource discovery script for Spark executors — the getTpusResources
# analogue of the reference's getGpusResources.sh (README.md:83-86 wiring:
#   spark.executor.resource.tpu.discoveryScript=this file
#   spark.executor.resource.tpu.amount=<chips per executor, normally 1>
#   spark.task.resource.tpu.amount=1
# ). TPU chips are single-tenant: unlike the reference's fractional
# gpu.amount=0.08 oversubscription (12 tasks sharing one GPU), one task owns
# one chip and parallelism comes from partition count (SURVEY.md §7 hard
# part #4).
#
# Prints the Spark ResourceInformation JSON: {"name": "tpu", "addresses": [...]}.
set -euo pipefail

# Preferred: ask the accelerator runtime. Works on Cloud TPU VMs where the
# libtpu device nodes are /dev/accel* (one per chip), and in environments
# exposing TPU_CHIPS_PER_HOST_BOUNDS / TPU_VISIBLE_DEVICES.
addresses=()

if [[ -n "${TPU_VISIBLE_DEVICES:-}" ]]; then
  IFS=',' read -r -a addresses <<< "${TPU_VISIBLE_DEVICES}"
elif compgen -G "/dev/accel*" > /dev/null; then
  for dev in /dev/accel*; do
    addresses+=("${dev#/dev/accel}")
  done
elif command -v python3 > /dev/null; then
  # Fallback: enumerate via JAX (slow path; only at executor bring-up).
  mapfile -t addresses < <(python3 - <<'PY' 2>/dev/null || true
import jax
for d in jax.devices():
    if d.platform == "tpu":
        print(d.id)
PY
)
fi

if [[ ${#addresses[@]} -eq 0 ]]; then
  echo '{"name": "tpu", "addresses": []}'
  exit 0
fi

printf '{"name": "tpu", "addresses": ['
for i in "${!addresses[@]}"; do
  [[ $i -gt 0 ]] && printf ','
  printf '"%s"' "${addresses[$i]}"
done
printf ']}\n'
