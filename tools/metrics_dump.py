"""Run a tiny instrumented train loop and print the Prometheus snapshot.

CI contract (tests/test_profiler_metrics.py greps this output): after a
few eager ops with backward, one eager collective, and a short
`Model.fit`, every metric name in EXPECTED_METRICS must appear in the
Prometheus-text dump with activity recorded. Exit status is non-zero
when one is missing, so the tool doubles as a smoke check that the
hot-path instrumentation stayed wired up.

Usage: JAX_PLATFORMS=cpu python tools/metrics_dump.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

EXPECTED_METRICS = (
    "paddle_tpu_dispatch_ops_total",
    "paddle_tpu_vjp_jit_cache_total",
    "paddle_tpu_jit_compiles_total",
    "paddle_tpu_jit_compile_seconds_total",
    "paddle_tpu_collective_calls_total",
    "paddle_tpu_collective_bytes_total",
    "paddle_tpu_grad_buckets",
    "paddle_tpu_train_steps_per_sec",
    "paddle_tpu_hapi_batches_total",
    # Pallas kernel autotuner (ISSUE 11): registered by importing
    # profiler.metrics; activity is exercised by the autotune tests
    "paddle_tpu_kernel_autotune_cache_hits_total",
    "paddle_tpu_kernel_autotune_cache_misses_total",
    "paddle_tpu_kernel_autotune_search_seconds_total",
    "paddle_tpu_kernel_autotune_candidates_rejected_parity_total",
    # Trace-discipline guards (ISSUE 12): registered by importing
    # profiler.metrics; activity is exercised by tests/test_tracelint
    # and the smoke tools' sanitize() wrappers
    "paddle_tpu_compile_watchdog_budget_exceeded_total",
    "paddle_tpu_compile_watchdog_transfer_guard_trips_total",
    # Request tracing + SLO plane (ISSUE 16): registered by importing
    # serving.metrics (tracing/slo mirror into these); activity is
    # exercised by tools/trace_smoke.py and tests/test_tracing.py.
    # CONTRACT_METRICS below greps the full set; these are the
    # representative names pinned here so a contract-table edit cannot
    # silently drop the observability plane from this dump.
    "paddle_tpu_serving_trace_requests_total",
    "paddle_tpu_serving_trace_events_total",
    "paddle_tpu_serving_slo_ttft_p95_seconds",
    "paddle_tpu_serving_slo_breaches_total",
    # Fleet control plane (ISSUE 17): registered by importing
    # serving.metrics; activity is exercised by tools/fleet_smoke.py
    # and tests/test_fleet.py (AOT boots, rolling upgrades, SLO-driven
    # scale events)
    "paddle_tpu_serving_fleet_replicas",
    "paddle_tpu_serving_fleet_boots_total",
    "paddle_tpu_serving_fleet_upgrades_total",
    "paddle_tpu_serving_fleet_scale_events_total",
    "paddle_tpu_serving_fleet_cold_start_seconds",
    # Device-resident multi-tick decode (ISSUE 18): registered by
    # importing serving.metrics; activity is exercised by
    # tools/multitick_smoke.py and tests/test_multitick.py (while_loop
    # trip counts, control-readback stalls, finish/overflow/reject
    # early-exit taxonomy)
    "paddle_tpu_serving_ticks_per_dispatch",
    "paddle_tpu_serving_host_stall_seconds_total",
    "paddle_tpu_serving_early_exits_total",
    # On-device speculation (ISSUE 19): mode gauge (off/host/device)
    # registered by importing serving.metrics; activity is exercised
    # by tools/multitick_smoke.py's speculative burst and
    # tests/test_multitick.py's identity matrix
    "paddle_tpu_serving_speculation_state",
    # Sharded graph engine + GraphSAGE lane (ISSUE 20): registered by
    # importing ps.graph.metrics (the grep below pulls the full
    # ps.graph.metrics.CONTRACT_METRICS set; activity is exercised by
    # tools/graph_smoke.py and tests/test_graph_engine.py —
    # sample-time histogram, frontier raw/unique counters, dedup
    # gauge, streaming add/remove counters, prefetch hit/repair/unused
    # taxonomy, edge-count gauge)
    "paddle_tpu_graph_sample_seconds",
    "paddle_tpu_graph_frontier_nodes_total",
)


def run_tiny_loop():
    """A few eager ops + one eager collective + a 2-epoch hapi fit on a
    synthetic dataset — touches every instrumented layer."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.parallel import collective

    # eager dispatch + VJP-jit cache (repeat the same op so the cache
    # records both a miss and hits)
    x = paddle.randn([8, 8])
    x.stop_gradient = False
    for _ in range(3):
        y = (x * x).sum()
        y.backward()
        x.clear_grad()

    # eager collective (identity at world_size 1; accounting still runs)
    collective.all_reduce(paddle.to_tensor(
        np.ones((16, 4), np.float32)))

    # bucketed grad reduction: the bucket-plan gauge publishes even on
    # the single-controller identity path
    from paddle_tpu.parallel.fleet_utils import fused_allreduce_gradients
    lin = paddle.nn.Linear(4, 4)
    (lin(paddle.to_tensor(np.ones((2, 4), np.float32))) ** 2) \
        .sum().backward()
    fused_allreduce_gradients(list(lin.parameters()))

    class DS(paddle.io.Dataset):
        def __len__(self):
            return 64

        def __getitem__(self, i):
            rng = np.random.RandomState(i)
            return (rng.rand(4).astype("float32"),
                    np.array([i % 2], np.int64))

    model = paddle.Model(paddle.nn.Sequential(
        paddle.nn.Linear(4, 8), paddle.nn.ReLU(),
        paddle.nn.Linear(8, 2)))
    opt = paddle.optimizer.SGD(0.1, parameters=model.parameters())
    model.prepare(opt, paddle.nn.CrossEntropyLoss())
    model.fit(DS(), epochs=2, batch_size=16, verbose=0)


def main(argv=None):
    from paddle_tpu.profiler import metrics

    # the serving-side contract names (engine, prefix cache, router —
    # serving.metrics.CONTRACT_METRICS) must be REGISTERED by import
    # alone: a renamed metric would silently break the dashboards and
    # the serving/router smoke greps, so this dump greps them too
    # (registration prints their TYPE lines; activity is the smokes'
    # job)
    from paddle_tpu.serving.metrics import CONTRACT_METRICS
    # same registration-by-import contract for the graph lane (ISSUE
    # 20): tools/graph_smoke.py greps activity, this dump greps names
    from paddle_tpu.ps.graph.metrics import (
        CONTRACT_METRICS as GRAPH_CONTRACT_METRICS)

    metrics.enable()
    try:
        run_tiny_loop()
        text = metrics.REGISTRY.to_prometheus()
    finally:
        metrics.disable()
    print(text)
    missing = [name for name in EXPECTED_METRICS
               + tuple(CONTRACT_METRICS)
               + tuple(GRAPH_CONTRACT_METRICS)
               if name not in text]
    if missing:
        print(f"MISSING METRICS: {missing}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
