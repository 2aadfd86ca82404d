#!/bin/bash
# Spin up an N-validator localnet from scratch, drive transactions at it,
# and assert the chain advances with converged app hashes — the
# one-command smoke the reference ships as `make localnet-start`
# (docker-compose) — here plain processes on one host.
#
# Usage: scripts/localnet.sh [N] [TARGET_HEIGHT] [BASE_PORT]
set -euo pipefail
cd "$(dirname "$0")/.."

N=${1:-4}
TARGET=${2:-5}
BASE_PORT=${3:-27656}
DIR=$(mktemp -d /tmp/tmtpu-localnet.XXXXXX)
PY=${PYTHON:-python}
# N node processes, at most one chip: a chip belongs to ONE process, so
# every node here is pinned to the CPU and verifies on the host. (A
# shared device is the verifyd sidecar's job — one daemon owns the chip.)
export JAX_PLATFORMS=cpu
export TMTPU_DISABLE_TPU=1

cleanup() {
  kill "${PIDS[@]}" 2>/dev/null || true
  wait 2>/dev/null || true
}
trap cleanup EXIT

echo "==> generating $N-validator testnet in $DIR"
$PY -m tendermint_tpu.cli testnet -v "$N" -o "$DIR" --base-port "$BASE_PORT" >/dev/null

PIDS=()
for i in $(seq 0 $((N - 1))); do
  $PY -m tendermint_tpu.cli --home "$DIR/node$i" start >"$DIR/node$i.log" 2>&1 &
  PIDS+=($!)
done
echo "==> started ${#PIDS[@]} nodes (logs in $DIR)"

rpc_port=$((BASE_PORT + 1))
status() {
  curl -s "http://127.0.0.1:$rpc_port/status" 2>/dev/null || true
}

echo "==> sending txs + waiting for height >= $TARGET"
for t in $(seq 1 120); do
  curl -s "http://127.0.0.1:$rpc_port/broadcast_tx_async?tx=%22k$t=v$t%22" >/dev/null 2>&1 || true
  H=$(status | $PY -c 'import json,sys
try: print(json.load(sys.stdin)["result"]["sync_info"]["latest_block_height"])
except Exception: print(0)')
  if [ "${H:-0}" -ge "$TARGET" ]; then
    echo "==> height $H reached"
    # cross-check app hashes at a common height across all nodes;
    # a node still gossip-lagged behind TARGET gets retried — only an
    # ACTUAL hash mismatch is divergence
    REF=""
    for i in $(seq 0 $((N - 1))); do
      p=$((BASE_PORT + 2 * i + 1))
      AH="?"
      for _try in $(seq 1 30); do
        AH=$(curl -s "http://127.0.0.1:$p/block?height=$TARGET" | $PY -c 'import json,sys
try: print(json.load(sys.stdin)["result"]["block"]["header"]["app_hash"])
except Exception: print("?")')
        [ "$AH" != "?" ] && break
        sleep 1
      done
      echo "    node$i app_hash@$TARGET = $AH"
      [ "$AH" = "?" ] && { echo "node$i never served block $TARGET"; exit 1; }
      [ -z "$REF" ] && REF="$AH"
      [ "$AH" = "$REF" ] || { echo "APP HASH DIVERGENCE"; exit 1; }
    done
    echo "==> localnet OK: $N nodes converged at height $TARGET"
    exit 0
  fi
  sleep 1
done
echo "localnet did not reach height $TARGET; last status:"
status
exit 1
