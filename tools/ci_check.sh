#!/usr/bin/env bash
# Fast correctness gate: repo lint + static program verification + the
# quick tier-1 subset, with the verifier armed (PADDLE_TPU_VERIFY=error)
# so every program the tests build must verify clean of error-severity
# diagnostics.  Full tier-1 stays the ROADMAP.md command; this script is
# the pre-push / CI smoke layer (a few minutes on a laptop CPU).
#
# Usage: tools/ci_check.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export PADDLE_TPU_DATASET="${PADDLE_TPU_DATASET:-synthetic}"

echo "== [1/14] repo lint (tools/lint.py) =="
python tools/lint.py

echo "== [2/14] static verification of example programs =="
python -m paddle_tpu.cli verify \
    examples/transformer_lm.py \
    examples/pipeline_transformer_lm.py \
    examples/serve_image_classifier.py \
    examples/dist_ckpt_worker.py

echo "== [3/14] fast tier-1 subset with PADDLE_TPU_VERIFY=error =="
# (TestSoftmax::test_grad is back in: its constant-loss degeneracy — the
# old finite-difference flake — is fixed via grad_output_weights)
PADDLE_TPU_VERIFY=error python -m pytest \
    tests/test_analysis.py \
    tests/test_registry.py \
    tests/test_basic_ops.py \
    tests/test_control_flow.py \
    tests/test_io.py \
    tests/test_cli.py \
    tests/test_debugger.py \
    -q -m 'not slow' -p no:cacheprovider

echo "== [4/14] observability + comm subset with PADDLE_TPU_METRICS=on =="
# the instrumented hot paths must behave identically with the metric
# instruments armed (docs/observability.md); test_comm.py also pins the
# bucketed wire path's backward compatibility both directions
PADDLE_TPU_METRICS=on python -m pytest \
    tests/test_observability.py \
    tests/test_executor_cache.py \
    tests/test_serving.py \
    tests/test_pserver.py \
    tests/test_comm.py \
    -q -m 'not slow' -p no:cacheprovider

echo "== [5/14] memory layer: fast book subset + memory plan with the optimizer armed =="
# the whole-program memory layer (donation plan, dead-var freeing,
# rename pass — docs/performance.md 'Memory') must leave training
# semantics untouched with the verifier also armed: the book models
# still converge and every optimized program verifies clean
PADDLE_TPU_MEMORY_OPTIMIZE=on PADDLE_TPU_VERIFY=error python -m pytest \
    tests/book/test_fit_a_line.py \
    tests/book/test_recognize_digits.py \
    tests/book/test_recommender_system.py \
    tests/test_memory_optimize.py \
    tests/test_memory_plan.py \
    -q -p no:cacheprovider


echo "== [6/14] elastic cluster: fast subset under chaos + metrics =="
# the elastic runtime (docs/resilience.md "Elastic clusters") must hold
# with the fault injector armed and the metric instruments on: the
# injected first-rebalance failure is retried by the controller's watch
# loop, and every view change/migration still lands its telemetry
PADDLE_TPU_FAULTS="cluster.rebalance:error:1" PADDLE_TPU_METRICS=on \
    python -m pytest \
    tests/test_elastic.py \
    -q -m 'not slow' -p no:cacheprovider
# the rebalance counters must be visible in a Prometheus dump
PADDLE_TPU_METRICS=on python - <<'EOF'
import numpy as np
from paddle_tpu.cloud.cluster import ClusterController
from paddle_tpu.cloud.registry import Lease, RegistryClient
from paddle_tpu.observability import exporters
from paddle_tpu.parallel.distributed_spliter import VarDesc
from tests.test_elastic import _sgd_server

params = {"w": np.ones(8, np.float32)}
srv, ep = _sgd_server(params)
ctl = ClusterController(min_pservers=1, poll_s=0.05)
ctl.serve(0)
ctl.start()
ctl.define([VarDesc("w", (8,), "float32")])
lease = Lease(RegistryClient(ctl.registry_addr), "pserver", ep, ttl_s=2.0)
assert ctl.wait_view(1, timeout_s=15) is not None, "no stable view"
text = exporters.prometheus_text()
for series in ("paddle_tpu_cluster_view_epoch",
               "paddle_tpu_cluster_rebalances_total",
               "paddle_tpu_cluster_membership_changes_total",
               "paddle_tpu_cluster_rebalance_seconds"):
    assert series in text, f"missing {series} in Prometheus dump"
lease.release()
srv.stop()
ctl.close()
print("elastic telemetry visible in Prometheus dump")
EOF

echo "== [7/14] generation serving: fast subset + Prometheus series =="
# the continuous-batching serving layer (docs/serving.md) must behave
# identically with the metric instruments armed, and every serving
# process must expose the generation series a fleet dashboard scrapes
PADDLE_TPU_METRICS=on python -m pytest \
    tests/test_generation_serving.py \
    -q -m 'not slow' -p no:cacheprovider
PADDLE_TPU_METRICS=on python - <<'EOF'
import numpy as np
import paddle_tpu as fluid
import paddle_tpu.core.framework as fw
from paddle_tpu.models.transformer import build_lm_paged_decoder
from paddle_tpu.observability import exporters
from paddle_tpu.serving import GenerationServer

fw.reset_unique_names()
startup, dec = build_lm_paged_decoder(23, 4, 4, d_model=16, n_heads=2,
                                      n_layers=1)
scope = fluid.Scope()
fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
states = {n: np.asarray(scope.find_var(n)) for n in dec.state_names}
# target doubles as its own draft: the speculative + prefix-cache
# paths run for real (proposals verified, prompt blocks hash-consed)
srv = GenerationServer(dec, states, slots=2, kv_blocks=8,
                       place=fluid.CPUPlace(),
                       draft_decoder=dec, draft_states=states,
                       spec_k=2)
assert srv.generate([1, 2, 3, 4], 6, timeout=60)
assert srv.generate([1, 2, 3, 4], 6, timeout=60)
st = srv.stats()
assert st["draft_proposed"] > 0, st
assert st["prefix_hits"] > 0, st
text = exporters.prometheus_text()
for series in ("paddle_tpu_serving_generation_requests_total",
               "paddle_tpu_serving_generated_tokens_total",
               "paddle_tpu_serving_decode_ticks_total",
               "paddle_tpu_serving_generation_shed_total",
               "paddle_tpu_serving_generation_seconds",
               "paddle_tpu_serving_first_token_seconds",
               "paddle_tpu_serving_kv_blocks_in_use",
               "paddle_tpu_serving_kv_pool_utilization",
               "paddle_tpu_serving_prefix_hits_total",
               "paddle_tpu_serving_prefix_misses_total",
               "paddle_tpu_serving_draft_proposed_total",
               "paddle_tpu_serving_draft_accepted_total",
               "paddle_tpu_serving_kv_bytes_resident"):
    assert series in text, f"missing {series} in Prometheus dump"
srv.close()
print("generation serving series visible in Prometheus dump "
      "(incl. prefix-cache + speculative-decoding series)")
EOF

echo "== [8/14] multichip sharding: spmd transpiler on the 8-device virtual mesh =="
# the mainline sharding path (docs/performance.md "Multichip sharding"):
# annotated Programs lower through ShardingTranspiler onto the proven
# dp/tp/pp executors, match serial + the composite.py oracle, and the
# sharding-consistency diagnostics verify clean with the verifier armed
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    PADDLE_TPU_VERIFY=error python -m pytest \
    tests/test_spmd_sharding.py \
    -q -m 'not slow' -p no:cacheprovider

echo "== [9/14] static cost analyzer: budget gate over the example configs =="
# the compile-free perf-regression gate (docs/analysis.md 'Budget
# gate'): every example config's static roofline / peak-HBM estimate
# must stay inside its checked-in budget, its bound verdict must not
# flip, and cost-metadata coverage must stay complete — with the
# verifier armed so the cost/comm/collective-safety passes run on
# every program the configs build
PADDLE_TPU_VERIFY=error python -m paddle_tpu.cli analyze \
    --budget tools/budgets.json \
    examples/transformer_lm.py \
    examples/pipeline_transformer_lm.py \
    examples/serve_image_classifier.py \
    examples/dist_ckpt_worker.py
# cli verify --json stays machine-parseable for editor/CI consumers
python -m paddle_tpu.cli verify --json examples/transformer_lm.py \
    | python -c "import json,sys; d=json.load(sys.stdin); \
assert not d['failed'] and d['programs'], d"


echo "== [10/14] concurrency analyzer: repo-wide lint + schedule-checked protocols =="
# the threaded runtimes (pserver wire protocol, elastic controller,
# serving scheduler, comm workers — docs/analysis.md 'Concurrency
# analysis') must stay free of unsuppressed error-severity concurrency
# findings (lock-order cycles, blocking-under-lock, thread hygiene),
# and the distributed protocols must hold their invariants (no
# deadlock, no lost shard copy, KV refcount balance, per-endpoint
# frame ordering) over every interleaving the schedule checker
# explores
python -m paddle_tpu.cli concurrency --sched

echo "== [11/14] fleet telemetry: mini-fleet federation + SLO gate =="
# the fleet telemetry plane (docs/observability.md "Fleet telemetry"):
# a real 1-trainer x 1-pserver + 1-replica fleet under
# PADDLE_TPU_METRICS=on, every member announcing its /metrics endpoint
# in the TTL-lease registry; the TelemetryCollector's federated dump
# must carry member-labeled series from all three kinds, the flight
# recorder must survive a SIGKILLed pserver, and the checked-in SLO
# baseline must hold against the dump
FLEET_PROM="$(mktemp -t paddle_fleet_XXXX.prom)"
PADDLE_TPU_METRICS=on python tools/mini_fleet.py --out "$FLEET_PROM"
python -m paddle_tpu.cli slo --check --spec tools/slo.json \
    --prom "$FLEET_PROM"
rm -f "$FLEET_PROM"



echo "== [12/14] autoscaling fleet: scale-out / SIGKILL / scale-in drill =="
# the ROADMAP-4 acceptance (docs/serving.md "Autoscaling"): an
# open-loop load ramp against a live router+autoscaler fleet triggers
# scale-out (no replica compiles after its warmup), a
# SIGKILLed replica mid-ramp is absorbed by the router's resume
# contract, the ramp-down scales back in via graceful drain — with
# ZERO failed requests — and the fleet-size / crash-loop / zero-failed
# SLOs hold on the federated dump
DRILL_PROM="$(mktemp -t paddle_drill_XXXX.prom)"
PADDLE_TPU_METRICS=on python tools/mini_fleet.py --drill autoscale \
    --out "$DRILL_PROM"
python -m paddle_tpu.cli slo --check --spec tools/slo.json \
    --prom "$DRILL_PROM"
rm -f "$DRILL_PROM"



echo "== [13/14] time attribution: phase / exemplar / straggler drill =="
# the time-attribution acceptance (docs/observability.md "Time
# attribution"): phase() overhead stays under 5% when the stack is
# off, a decode-delay fault on one replica dominates the fleet
# why-table, a delayed pserver is flagged as a straggler from the
# comm-round histograms within one collector window, and a p99
# exemplar on the serving histogram joins to a tail-sampled Chrome
# trace via `cli trace-of`
ATTR_PROM="$(mktemp -t paddle_attr_XXXX.prom)"
PADDLE_TPU_METRICS=on python tools/mini_fleet.py --drill attribution \
    --out "$ATTR_PROM"
python -m paddle_tpu.cli slo --check --spec tools/slo.json \
    --prom "$ATTR_PROM"
rm -f "$ATTR_PROM"

echo "== [14/14] kernels: Pallas/XLA parity, selection, TPU lowering =="
# greedy decode through the paged-attention kernel (Pallas interpreter
# on the CPU) must be BIT-identical to the XLA gather path with runtime
# verification armed; selection must be the benchmark geometries'
# documented one; every kernel must lower for the TPU
# (docs/performance.md "Kernel selection")
JAX_PLATFORMS=cpu PADDLE_TPU_VERIFY=error python -m pytest \
    tests/test_paged_attention.py tests/test_kernels_lower_tpu.py \
    tests/test_grouped_matmul.py -q -m 'not slow' -p no:cacheprovider

echo "ci_check: all green"
