#!/bin/sh
# Env -> CLI flag translation (the reference pattern: BACKEND_URLS/PORT/
# TIMEOUT envs feeding the binary; here MODELS replaces backend URLs).
# Args accumulate via `set --` so values with spaces survive quoting.
set -e

set -- --no-tui --host 0.0.0.0
[ -n "${MODELS:-}" ] && set -- "$@" --models "$MODELS"
[ -n "${CHECKPOINTS:-}" ] && set -- "$@" --checkpoints "$CHECKPOINTS"
[ -n "${PORT:-}" ] && set -- "$@" --port "$PORT"
[ -n "${TIMEOUT:-}" ] && set -- "$@" --timeout "$TIMEOUT"
[ -n "${TP:-}" ] && set -- "$@" --tp "$TP"
[ -n "${DP:-}" ] && set -- "$@" --dp "$DP"
[ -n "${EP:-}" ] && set -- "$@" --ep "$EP"
[ -n "${PAGE_SIZE:-}" ] && set -- "$@" --page-size "$PAGE_SIZE"
[ -n "${NUM_PAGES:-}" ] && set -- "$@" --num-pages "$NUM_PAGES"
[ "${SPMD:-}" = "true" ] && set -- "$@" --spmd
[ -n "${REPLICAS:-}" ] && set -- "$@" --replicas "$REPLICAS"
[ -n "${REPLICA_URLS:-}" ] && set -- "$@" --replica-urls "$REPLICA_URLS"
[ -n "${PLACEMENT:-}" ] && set -- "$@" --placement "$PLACEMENT"
[ -n "${SCHEDULER:-}" ] && set -- "$@" --scheduler "$SCHEDULER"
[ -n "${DRAIN_TIMEOUT_S:-}" ] && set -- "$@" --drain-timeout-s "$DRAIN_TIMEOUT_S"
[ "${MIGRATE:-}" = "false" ] && set -- "$@" --no-migrate
[ -n "${MIGRATE_TIMEOUT_S:-}" ] && set -- "$@" --migrate-timeout-s "$MIGRATE_TIMEOUT_S"
[ -n "${TIERS:-}" ] && set -- "$@" --tiers "$TIERS"
[ -n "${ROUTER_OVERHEAD_BUDGET_MS:-}" ] && set -- "$@" --router-overhead-budget-ms "$ROUTER_OVERHEAD_BUDGET_MS"
[ "${AUTOSCALE:-}" = "true" ] && set -- "$@" --autoscale
[ -n "${MIN_REPLICAS:-}" ] && set -- "$@" --min-replicas "$MIN_REPLICAS"
[ -n "${MAX_REPLICAS:-}" ] && set -- "$@" --max-replicas "$MAX_REPLICAS"
[ -n "${SCALE_COOLDOWN_S:-}" ] && set -- "$@" --scale-cooldown-s "$SCALE_COOLDOWN_S"
[ -n "${PREEMPTIBLE:-}" ] && set -- "$@" --preemptible "$PREEMPTIBLE"
[ "${FEDERATE_METRICS:-}" = "false" ] && set -- "$@" --no-federate-metrics
[ -n "${MAX_SLOTS:-}" ] && set -- "$@" --max-slots "$MAX_SLOTS"
[ "${HA:-}" = "true" ] && set -- "$@" --ha
[ -n "${STANDBY_OF:-}" ] && set -- "$@" --standby-of "$STANDBY_OF"
[ -n "${TAKEOVER_GRACE_S:-}" ] && set -- "$@" --takeover-grace-s "$TAKEOVER_GRACE_S"
[ -n "${WAL_DIR:-}" ] && set -- "$@" --wal-dir "$WAL_DIR"
[ -n "${WAL_FSYNC_MS:-}" ] && set -- "$@" --wal-fsync-ms "$WAL_FSYNC_MS"
[ -n "${JOURNAL_SAMPLE:-}" ] && set -- "$@" --journal-sample "$JOURNAL_SAMPLE"
[ -n "${STOP_GRACE_S:-}" ] && set -- "$@" --stop-grace-s "$STOP_GRACE_S"
[ -n "${BLOCKLIST:-}" ] && set -- "$@" --blocklist "$BLOCKLIST"
[ "${ALLOW_ALL_ROUTES:-}" = "true" ] && set -- "$@" --allow-all-routes
[ "${FAKE_ENGINE:-}" = "true" ] && set -- "$@" --fake-engine

cd /app
exec python -m ollamamq_tpu.cli "$@"
