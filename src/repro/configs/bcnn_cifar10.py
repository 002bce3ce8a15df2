"""The paper's own model: the 9-layer CIFAR-10 BCNN of Table 2.

Not one of the 10 assigned LM architectures — this is the reproduction
target itself (core/bcnn.py builds it; the launchers and
examples/train_bcnn_cifar10.py consume this module). Kernel choices are not
configured here: they come from `core/execution_plan.py::default_plan` or
the tuner (`kernels/autotune.py`) as one ``ExecutionPlan``.
"""
from __future__ import annotations

from repro.core.bcnn import CONV_SPECS, FC_SPECS          # noqa: F401
from repro.core.throughput import (BCNN_CONV_LAYERS,      # noqa: F401
                                   BCNN_FC_SPECS, FREQ_HZ, PAPER_FPS,
                                   PAPER_TABLE3, PAPER_TOPS)

NAME = "bcnn-cifar10"
INPUT_SHAPE = (32, 32, 3)          # CIFAR-10 RGB
N_CLASSES = 10

# Training defaults (train/bcnn_train.py, launch/train_bcnn.py): the
# Courbariaux/Bengio recipe's CPU-scale operating point — ~2 min wall for
# the full 300 steps, --steps 60 for a fast check — and the step-atomic
# checkpoint cadence of the restartable loop.
TRAIN_STEPS = 300
TRAIN_BATCH = 64
TRAIN_LR = 2e-3
TRAIN_CKPT_EVERY = 50

# Streaming-service default (serve/bcnn_engine.py, launch/serve_bcnn.py):
# slot count for the continuously-stepped engine.
SERVE_N_SLOTS = 4

# Fleet serving (serve/router.py, launch/serve_bcnn.py --replicas): the
# async request router over N replicated engines. ROUTER_REPLICAS = 1
# keeps the single-engine path (the router tier is opt-in);
# ROUTER_MAX_QUEUE bounds the admission backlog (past it requests are
# shed with a typed RouterOverload); ONLINE_DEADLINE_S is the latency SLO
# of the "online" traffic class (the "bulk" class is best-effort);
# PRIORITY_MIX is the default offered-traffic composition of the mixed
# Poisson driver ("class=weight,...").
ROUTER_REPLICAS = 1
ROUTER_MAX_QUEUE = 256
ONLINE_DEADLINE_S = 0.5
PRIORITY_MIX = "online=3,bulk=1"

# Elastic fleet autoscaling + mixed-traffic co-scheduling
# (serve/autoscale.py, serve/router.py, launch/serve_bcnn.py --autoscale):
# the replica count tracks offered load between hysteresis watermarks
# (pressure = outstanding images per fleet slot; the config REQUIRES
# down < up/2 — the oscillation-free invariant), while bulk batches are
# co-scheduled as BULK_CHUNK-image micro-chunks through the same
# priority/EDF scheduler with ONLINE_RESERVE per-replica dispatch slots
# bulk may never occupy.
AUTOSCALE_MIN_REPLICAS = 1
AUTOSCALE_MAX_REPLICAS = 4
AUTOSCALE_UP_WATERMARK = 2.0
AUTOSCALE_DOWN_WATERMARK = 0.25
AUTOSCALE_WINDOW_S = 0.1
AUTOSCALE_COOLDOWN_S = 0.5
AUTOSCALE_INTERVAL_S = 0.02
ONLINE_RESERVE = 1
BULK_CHUNK = 2

# Stage-pipelined deployment forward (parallel/bcnn_pipeline.py): number of
# cost-balanced pipeline stages the packed 9-layer forward is cut into
# (1 = single-device make_packed_forward, the default) and the micro-batch
# granule streamed through them.
PIPELINE_STAGES = 1
PIPELINE_MICRO_BATCH = 1

# Data-parallel batch serving (parallel/bcnn_data_parallel.py): the
# paper's large-batch Fig. 7 scenario. DATA_SHARDS replicates the packed
# network over that many devices and splits bulk batches across them
# (0 = bulk path disabled — slot streaming only); DATA_MICRO_BATCH is the
# per-shard granule, so DATA_SHARDS × DATA_MICRO_BATCH is the one jit'd
# chunk shape (and the default BCNNEngine.classify_batch routing
# threshold).
DATA_SHARDS = 0
DATA_MICRO_BATCH = 8
