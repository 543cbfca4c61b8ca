#!/usr/bin/env bash
# Stall-tolerant training launcher: runs train.py, watches the run's log for
# progress, and on a stall (no log writes for STALL_SECS — e.g. a device
# runtime that stops answering mid-run) kills the process and resumes from
# the run's checkpoints with --load. Training survives infrastructure flakes
# without operator attention (the reference had no crash-resume beyond manual
# --load either — SURVEY.md §5 checkpoint/resume).
#
# Usage: scripts/run_with_resume.sh LOGDIR MAX_RESTARTS STALL_SECS -- <train.py args...>
# The train args must include --logdir LOGDIR and NOT --load (the launcher
# adds --load LOGDIR/checkpoints whenever that directory exists, so re-running
# the same command over a prior run's logdir RESUMES it, never restarts it).
set -u
LOGDIR=$1; MAX_RESTARTS=$2; STALL_SECS=$3; shift 3
[ "$1" = "--" ] && shift
HERE=$(cd "$(dirname "$0")/.." && pwd)

attempt=0
while :; do
  args=("$@")
  # resume whenever a FINALIZED checkpoint exists — including a FRESH
  # launcher invocation over a prior run's logdir (restarting from step 0
  # would clobber the existing checkpoints). Gate on checkpoint.json's
  # non-null "latest" (written only after wait_until_finished), NOT on the
  # dir: CheckpointManager creates the dir at startup, so a stall-kill
  # before the first save would otherwise make every subsequent attempt
  # --load an empty dir, crash with exit 1, and burn MAX_RESTARTS on a
  # run that never trained (same gate as launch_multihost.sh).
  if [ -f "$LOGDIR/checkpoints/checkpoint.json" ] && \
     python3 -c 'import json,sys; sys.exit(0 if json.load(open(sys.argv[1])).get("latest") is not None else 1)' \
       "$LOGDIR/checkpoints/checkpoint.json" 2>/dev/null; then
    args+=(--load "$LOGDIR/checkpoints")
  fi
  echo "[run_with_resume] attempt $attempt: python train.py ${args[*]}" >&2
  # setsid: own process group, so the stall kill reaps the trainer AND its
  # spawned children without touching unrelated processes on the machine
  setsid python "$HERE/train.py" "${args[@]}" &
  pid=$!
  start=$(date +%s)
  # watchdog: poll the log mtime; kill on stall. Progress is measured
  # against max(attempt start, log mtime) so a stale log from a PREVIOUS
  # attempt can't kill this one, and until THIS attempt's first log write
  # (startup + XLA compile can exceed STALL_SECS) the threshold gets an
  # extra 600s of grace.
  while kill -0 $pid 2>/dev/null; do
    sleep 30
    log="$LOGDIR/log.log"
    last=$start
    thresh=$(( STALL_SECS + 600 ))
    if [ -f "$log" ]; then
      m=$(stat -c %Y "$log")
      if [ "$m" -gt "$last" ]; then
        last=$m
        thresh=$STALL_SECS
      fi
    fi
    age=$(( $(date +%s) - last ))
    if [ $age -gt $thresh ]; then
      echo "[run_with_resume] stall: no progress for ${age}s — killing group $pid" >&2
      kill -- -$pid 2>/dev/null; sleep 5; kill -9 -- -$pid 2>/dev/null
      break
    fi
  done
  wait $pid; rc=$?
  if [ $rc -eq 0 ]; then
    echo "[run_with_resume] finished cleanly" >&2
    exit 0
  fi
  attempt=$((attempt + 1))
  if [ $attempt -gt $MAX_RESTARTS ]; then
    echo "[run_with_resume] giving up after $MAX_RESTARTS restarts (rc=$rc)" >&2
    exit $rc
  fi
done
