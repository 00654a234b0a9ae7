#!/bin/bash
# Two-process data-parallel MNIST — the reference's mpi.conf story
# (2 workers on localhost, example/MNIST/mpi.conf) without MPI or
# parameter-server processes: each process contributes its local devices
# to ONE global mesh (jax.distributed over Gloo on CPU, DCN on TPU pods),
# and gradient all-reduce replaces the PS push/pull.
#
# This demo runs on any machine: 2 processes x 4 virtual CPU devices =
# an 8-device global mesh. On a real multi-host TPU pod, drop the two
# exports, point coordinator= at host 0, and set worker_rank per host.
#
# Usage: ./run_multihost.sh   (after ./run.sh or ./run.sh --synth for data)
set -e
cd "$(dirname "$0")"
REPO=../..
[ -f data/train-images-idx3-ubyte.gz ] || { echo "run ./run.sh first"; exit 1; }

export XLA_FLAGS="--xla_force_host_platform_device_count=4"
export JAX_PLATFORMS=cpu
COORD=127.0.0.1:9911
# batch 96: the global batch must divide across the 8 mesh devices
ARGS="coordinator=$COORD num_worker=2 dev=cpu:0-7 num_round=3 batch_size=96 model_dir=models_mh"
mkdir -p models_mh

python "$REPO/bin/cxxnet" MNIST.conf $ARGS worker_rank=1 &
W1=$!
python "$REPO/bin/cxxnet" MNIST.conf $ARGS worker_rank=0
wait $W1
echo "multihost run finished"
