#!/usr/bin/env bash
# One-command LOCAL bring-up with restart-on-failure supervision — the
# container-less analogue of `docker compose up` and of the reference's
# `run.sh` (reference: run.sh:32 `docker stack deploy`,
# docker-compose.yml:3-6 restart policy).
#
#   deploy/run_local.sh [N_AGENTS]     (default 0)
#
# One process per chip: a TPU belongs to the process that initialized
# it, and the API server initializes the backend at boot and takes
# every chip of the host.  So by default this starts exactly ONE
# chip-owning process, the API server; in-process POST /train/horovod
# already drives all local chips.  N_AGENTS >= 1 is the multi-host
# topology (coordinator + one agent per host) REHEARSED on one host:
# the agents are pinned to the CPU here, because they cannot share the
# server's chips.  Real multi-host agents run one per TPU host
# (deploy/docker-compose.yml, deploy/k8s.yaml).
#
# Env: LO_TPU_API_PORT (default 8080), LO_COORD_PORT (default 7070),
#      LO_TPU_STORE_ROOT / LO_TPU_VOLUME_ROOT (default ./lo-data/...).
# Stops everything on Ctrl-C / SIGTERM.

set -u

N_AGENTS="${1:-0}"
API_PORT="${LO_TPU_API_PORT:-8080}"
COORD_PORT="${LO_COORD_PORT:-7070}"
DATA_ROOT="${LO_DATA_ROOT:-$PWD/lo-data}"
export LO_TPU_API_PORT="$API_PORT"
export LO_TPU_STORE_ROOT="${LO_TPU_STORE_ROOT:-$DATA_ROOT/store}"
export LO_TPU_VOLUME_ROOT="${LO_TPU_VOLUME_ROOT:-$DATA_ROOT/volumes}"
# Cluster mode: POST /train/horovod fans out to the agents below
# (LO_CLUSTER_MODE=0 keeps fits in-process in the API server).
if [ "${LO_CLUSTER_MODE:-1}" = "1" ] && [ "$N_AGENTS" -ge 2 ]; then
  export LO_TPU_TASK_COORDINATOR="127.0.0.1:$COORD_PORT"
  export LO_TPU_WORLD_SIZE="$N_AGENTS"
fi
mkdir -p "$LO_TPU_STORE_ROOT" "$LO_TPU_VOLUME_ROOT"

PIDS=()

# Supervise: restart the role if it exits non-zero (the reference's
# on-failure policy); clean exit (0) ends supervision.  Each supervisor
# runs in its OWN process group (setsid) so cleanup can kill the whole
# tree — background subshells share the script's pgid, and killing just
# the subshell would orphan the python service it spawned.
supervise() {
  local name="$1"; shift
  local cmd
  printf -v cmd '%q ' "$@"
  setsid bash -c '
    while true; do
      '"$cmd"'
      code=$?
      if [ "$code" -eq 0 ]; then
        echo "['"$name"'] exited cleanly" >&2
        break
      fi
      echo "['"$name"'] exited with $code — restarting in 1s" >&2
      sleep 1
    done
  ' &
  PIDS+=($!)
}

cleanup() {
  echo "stopping cluster" >&2
  for pid in "${PIDS[@]}"; do
    kill -- -"$pid" 2>/dev/null || kill "$pid" 2>/dev/null || true
  done
  # Bounded grace, then KILL the groups: the supervisors live in
  # their OWN process groups (setsid), unreachable from a caller's
  # killpg on THIS script — if cleanup stalls on a saturated box and
  # the caller SIGKILLs us mid-wait, un-KILLed groups would orphan
  # their services (observed: a coordinator+api+agent trio surviving
  # a test teardown for an hour, stealing a core's worth of probes).
  for _ in $(seq 1 20); do
    alive=0
    for pid in "${PIDS[@]}"; do
      kill -0 "$pid" 2>/dev/null && alive=1
    done
    [ "$alive" = 0 ] && break
    sleep 0.5
  done
  for pid in "${PIDS[@]}"; do
    kill -9 -- -"$pid" 2>/dev/null || true
  done
  wait 2>/dev/null
  exit 0
}
trap cleanup INT TERM

if [ "$N_AGENTS" -ge 1 ]; then
  supervise coordinator python -m learningorchestra_tpu coordinator \
    --host 127.0.0.1 --port "$COORD_PORT"
fi
# Port on the command line (redundant with LO_TPU_API_PORT) so the
# process is identifiable by pgrep/pkill for teardown sweeps.
supervise api python -m learningorchestra_tpu serve --port "$API_PORT"
# Store HA (LO_HA_STANDBY=1): a warm standby ships the primary's WALs
# and promotes itself on sustained health-check failure — the mongo
# replica set's automatic election (store/ha.py).  A fenced old
# primary's restart exits cleanly, ending its supervision loop.
if [ "${LO_HA_STANDBY:-0}" = "1" ]; then
  STANDBY_PORT="${LO_HA_STANDBY_PORT:-$((API_PORT + 1))}"
  # Generous takeover window (2 s x 15 = 30 s dead, matching the
  # compose manifest): a supervised api restart pays ~10 s of python
  # imports, which must read as a blip, not a dead primary.
  #
  # LO_HA_TRANSPORT=http ships WALs over the primary's /replication
  # routes instead of reading its store directory — the no-shared-
  # storage mode compose/k8s use (store/ha.py); the default reads
  # through the filesystem, which on ONE host is the same disk anyway.
  STORE_ARGS=()
  if [ "${LO_HA_TRANSPORT:-fs}" != "http" ]; then
    STORE_ARGS=(--primary-store "$LO_TPU_STORE_ROOT")
  fi
  supervise standby python -m learningorchestra_tpu standby \
    --primary "127.0.0.1:$API_PORT" \
    ${STORE_ARGS[@]+"${STORE_ARGS[@]}"} \
    --replica "$DATA_ROOT/store-replica" \
    --port "$STANDBY_PORT" --host 127.0.0.1 \
    --interval 2 --misses 15
fi
if [ "$N_AGENTS" -ge 1 ]; then
  echo "agents share this host with the API server, which owns its" \
    "chips: starting them on the CPU (JAX_PLATFORMS=cpu)" >&2
fi
for i in $(seq 1 "$N_AGENTS"); do
  supervise "agent$i" env JAX_PLATFORMS=cpu \
    python -m learningorchestra_tpu agent \
    --coordinator "127.0.0.1:$COORD_PORT" --id "agent$i"
done

echo "up: api=:$API_PORT coordinator=:$COORD_PORT agents=$N_AGENTS" >&2
wait
