#!/usr/bin/env bash
# Launcher — the scripts/bigdl.sh + dist/conf/spark-bigdl.conf analogue.
#
# The reference launches one JVM executor per node via spark-submit with
# required conf (locality off, min-resources 1.0, speculation off) and
# env (KMP_AFFINITY, OMP_NUM_THREADS).  The TPU rebuild launches one JAX
# process per host; multi-host bring-up rides the same env-var contract
# Engine.init reads (SURVEY.md §2.5 "spark-submit remains only as a
# launcher").
#
# Single host:
#   scripts/bigdl_tpu.sh python -m bigdl_tpu.models.lenet -e 2
#
# Multi-host (run on every host, same coordinator):
#   BIGDL_COORDINATOR_ADDRESS=host0:8476 \
#   BIGDL_NUM_PROCESSES=4 BIGDL_PROCESS_ID=<i> \
#   scripts/bigdl_tpu.sh python -m bigdl_tpu.models.resnet --distributed
#
# Under Spark, set these from the executor context:
#   BIGDL_COORDINATOR_ADDRESS=$(spark-conf spark.driver.host):8476
#   BIGDL_NUM_PROCESSES=$SPARK_EXECUTOR_INSTANCES
#   BIGDL_PROCESS_ID=$SPARK_EXECUTOR_ID

set -euo pipefail

# --- reference env parity -------------------------------------------------
# the reference pins MKL threading (OMP_NUM_THREADS=1, KMP_AFFINITY) so
# Spark task threads don't oversubscribe; on TPU the host-side analogue
# keeps BLAS single-threaded for the feeding path and leaves the chip to
# XLA.
export OMP_NUM_THREADS="${OMP_NUM_THREADS:-1}"
export KMP_AFFINITY="${KMP_AFFINITY:-granularity=fine,compact,1,0}"

# pass through the multi-host contract if set
: "${BIGDL_COORDINATOR_ADDRESS:=}"
: "${BIGDL_NUM_PROCESSES:=}"
: "${BIGDL_PROCESS_ID:=}"

REPO_ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
export PYTHONPATH="${REPO_ROOT}${PYTHONPATH:+:$PYTHONPATH}"

if [[ $# -eq 0 ]]; then
    echo "usage: $0 <command> [args...]" >&2
    echo "  e.g. $0 python -m bigdl_tpu.models.lenet -e 2" >&2
    exit 2
fi

exec "$@"
