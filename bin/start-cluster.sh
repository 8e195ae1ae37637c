#!/usr/bin/env bash
# Local cluster bootstrap (ref: flink-dist bin/start-cluster.sh):
# one coordinator + one runner per host entry, HA-ready when
# FLINK_TPU_HA_DIR points at shared storage.
#
# One runner per chip host, by design: a chip belongs to one process at
# a time, so a second runner on the same chip host cannot get it (it
# fails or hangs at backend start-up). One runner drives every chip of
# its host (cluster.mesh-devices). The coordinator never initializes a
# JAX backend and may share the host.
set -euo pipefail

REPO="$(cd "$(dirname "$0")/.." && pwd)"
export PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}"

PORT="${FLINK_TPU_PORT:-6123}"
REST_PORT="${FLINK_TPU_REST_PORT:-8081}"
HA_DIR="${FLINK_TPU_HA_DIR:-}"
PIDDIR="${FLINK_TPU_PID_DIR:-/tmp/flink-tpu}"
mkdir -p "$PIDDIR"

coord_args=(--port "$PORT" --rest-port "$REST_PORT")
runner_args=(--coordinator "127.0.0.1:$PORT")
if [[ -n "$HA_DIR" ]]; then
  coord_args+=(--ha-dir "$HA_DIR")
  runner_args=(--ha-dir "$HA_DIR")
fi

python -m flink_tpu.runtime.coordinator "${coord_args[@]}" \
  > "$PIDDIR/coordinator.log" 2>&1 &
echo $! > "$PIDDIR/coordinator.pid"
echo "coordinator on :$PORT (rest :$REST_PORT), log $PIDDIR/coordinator.log"

sleep 2
python -m flink_tpu.runtime.runner "${runner_args[@]}" \
  > "$PIDDIR/runner.log" 2>&1 &
echo $! > "$PIDDIR/runner.pid"
echo "runner started, log $PIDDIR/runner.log"
