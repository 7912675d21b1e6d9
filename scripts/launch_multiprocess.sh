#!/usr/bin/env bash
# Rehearse a P-process x D-device multi-host topology on one machine.
#
# Spawns P local processes, each with D fake host (CPU) devices, wired
# together through jax.distributed's coordination service exactly like P
# real hosts would be — so a laptop or CI runner can exercise the
# multi-process bring-up path (process enumeration, global device
# visibility, per-process compute) before anyone buys hardware.  Note
# the CPU backend does not implement cross-process computations
# (repro/launch/distributed.py module docstring); this rehearses
# BRING-UP, while the single-process N-virtual-device mesh (multihost CI
# lane) exercises the collective code paths.
#
#     scripts/launch_multiprocess.sh [-p procs] [-d devices-per-proc] \
#         [-P coordinator-port] [-- cmd args...]
#
# Default command is the bring-up smoke; pass your own module after --
# to run any launcher under the runtime, e.g.
#
#     scripts/launch_multiprocess.sh -p 2 -d 4 -- \
#         python -m repro.launch.distributed --smoke
set -euo pipefail
cd "$(dirname "$0")/.."

PROCS=2
DEVICES=4
PORT="${REPRO_COORDINATOR_PORT:-$(( (RANDOM % 2000) + 27000 ))}"

while getopts "p:d:P:h" opt; do
  case "$opt" in
    p) PROCS="$OPTARG" ;;
    d) DEVICES="$OPTARG" ;;
    P) PORT="$OPTARG" ;;
    h) grep '^#' "$0" | sed 's/^# \{0,1\}//'; exit 0 ;;
    *) exit 2 ;;
  esac
done
shift $((OPTIND - 1))

if [ "$#" -gt 0 ]; then
  CMD=("$@")
else
  CMD=(python -m repro.launch.distributed --smoke
       --expect-processes "$PROCS")
fi

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export REPRO_COORDINATOR_ADDRESS="127.0.0.1:${PORT}"
export REPRO_NUM_PROCESSES="$PROCS"
export REPRO_LOCAL_DEVICE_COUNT="$DEVICES"
# A CPU rehearsal by design: P processes must never contend for a chip
# (only one process at a time may hold a TPU).
export JAX_PLATFORMS=cpu
# XLA_FLAGS must come from repro.launch.env inside each process, not
# from here — an exported flag would leak into unrelated children.
unset XLA_FLAGS

PIDS=()
for ((i = 0; i < PROCS; i++)); do
  REPRO_PROCESS_ID="$i" "${CMD[@]}" &
  PIDS+=($!)
done

FAIL=0
for pid in "${PIDS[@]}"; do
  wait "$pid" || FAIL=1
done
if [ "$FAIL" -ne 0 ]; then
  echo "launch_multiprocess: at least one process failed" >&2
  exit 1
fi
echo "launch_multiprocess: ${PROCS} processes x ${DEVICES} devices OK"
