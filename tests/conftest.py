"""Test harness: force an 8-device virtual CPU mesh BEFORE jax initializes.

The sandbox has no accelerator; all sharding tests run
on xla_force_host_platform_device_count=8 CPU devices (SURVEY.md §4
"multi-node-without-a-cluster"). Swarm tests additionally spawn real
localhost processes.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # for subprocesses spawned by swarm tests

from distributedvolunteercomputing_tpu.utils.jaxenv import pin_platform  # noqa: E402

pin_platform("cpu", min_host_devices=8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: payload-scale / long-running tests (opt-in: -m slow or DVC_RUN_SLOW=1)"
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection tests (drop/delay/corrupt/partition, fault "
        "schedules, deadline-bounded degradation) — in the default lane, and "
        "selectable on their own with -m chaos",
    )
    config.addinivalue_line(
        "markers",
        "transport: wire/pool/framing tests (connection pooling, rid demux, "
        "chunked payload streaming, per-peer counters, RPC-throughput "
        "smoke) — in the default lane, and selectable on their own with "
        "-m transport",
    )
    config.addinivalue_line(
        "markers",
        "aggregation: streaming leader-aggregation tests (tile pipeline, "
        "request sinks, streaming<->dense equivalence, bench smoke) — in "
        "the default lane, and selectable on their own with -m aggregation",
    )
    config.addinivalue_line(
        "markers",
        "failover: leader-failover tests (epoch fencing, successor "
        "election, recovery rounds, kill-at-phase matrix, leader-kill "
        "chaos smoke) — in the default lane, and selectable on their own "
        "with -m failover",
    )
    config.addinivalue_line(
        "markers",
        "mesh_codec: on-mesh data-path tests (bf16 codec, device tile "
        "folds, mean folder, sharded/pallas equivalence, degraded-slice "
        "fallback, codec bench smoke) — in the default lane, and "
        "selectable on their own with -m mesh_codec",
    )
    config.addinivalue_line(
        "markers",
        "mesh_collective: fused ring reduce-scatter/all-gather tests "
        "(interpret-mode kernel equivalence vs host/staged folds, eager "
        "xla ingest, NaN propagation, mid-round degrade, aggregator "
        "parity, fused bench smoke) — in the default lane, and selectable "
        "on their own with -m mesh_collective",
    )
    config.addinivalue_line(
        "markers",
        "multigroup: rotating multi-group schedule tests (grid partition, "
        "Moshpit mixing bound, group-scoped rounds, group-local failover, "
        "per-group stats rollups, scale-bench smoke) — in the default "
        "lane, and selectable on their own with -m multigroup",
    )
    config.addinivalue_line(
        "markers",
        "controlplane: replicated control-plane tests (replica election + "
        "key-range shard handoff, fenced stale-write rejection, batched "
        "heartbeat exchange, failover client + AIMD backoff, retiring "
        "tombstone, coordinator-kill chaos smoke, batching-vs-per-message "
        "bench smoke) — in the default lane, and selectable on their own "
        "with -m controlplane",
    )
    config.addinivalue_line(
        "markers",
        "hierarchy: hierarchical (zone-aware) scheduling tests (two-level "
        "grid, per-level mixing bound, zone-local failover, bandwidth-"
        "weighted leader election, per-pair link model, per-zone rollups, "
        "cross-zone-bytes bench smoke) — in the default lane, and "
        "selectable on their own with -m hierarchy",
    )
    config.addinivalue_line(
        "markers",
        "telemetry: telemetry-plane tests (metrics registry + scrape, "
        "cross-volunteer round tracing / frame-meta trace propagation, "
        "flight recorder, stats() snapshot semantics, coord.status "
        "telemetry schema, structured JSONL logging, overhead smoke) — in "
        "the default lane, and selectable on their own with -m telemetry",
    )
    config.addinivalue_line(
        "markers",
        "health: training-health telemetry tests (seeded random-projection "
        "sketch estimator vs direct parameter dispersion, gradient-mass "
        "accounting balance across the deadline/abort/fence matrix, "
        "per-peer contribution-quality attribution + flagging, "
        "--no-health-probe end-to-end plumbing, coord.status health "
        "schema, health-probe overhead smoke) — in the default lane, and "
        "selectable on their own with -m health",
    )
    config.addinivalue_line(
        "markers",
        "tailopt: tail-optimal aggregation tests (per-tile arrival "
        "scoreboard, hedged range re-requests + (peer, tile, fence) "
        "idempotency property test, recovered-mass accounting, summand "
        "redundancy XOR decode, AIMD hedge budget, heavy-tailed link "
        "jitter, hedged-vs-drop bench smoke failing loudly below the "
        "lost-mass bar) — in the default lane, and selectable on their "
        "own with -m tailopt",
    )
    config.addinivalue_line(
        "markers",
        "controller: closed-loop adaptive-controller tests (decision "
        "hysteresis property tests — noisy in-band series produce zero "
        "transitions, a step change exactly one per knob — epoch-fence "
        "application, per-level deadline divergence, regime-folded hedge "
        "budget, dense-wire selection + schema re-key, cadence learning, "
        "coord.status controller schema walk, --no-adapt end-to-end "
        "plumbing, controller overhead smoke) — in the default lane, and "
        "selectable on their own with -m controller",
    )
    config.addinivalue_line(
        "markers",
        "watchdog: swarm-watchdog tests (online baselines + anomaly "
        "detectors with hysteresis/cooldown, SLO burn-rate windows, "
        "alert lifecycle + flight severity, incremental flight cursor, "
        "Prometheus exposition + /metrics endpoint, coord.status "
        "slo/alerts schema walk, --no-watchdog end-to-end plumbing, "
        "watchdog overhead smoke) — in the default lane, and selectable "
        "on their own with -m watchdog",
    )
    config.addinivalue_line(
        "markers",
        "sharding: zone-sharded training tests (HRW shard map stability "
        "under churn, generation fencing both ends, fenced re-shard + "
        "hedged shard recovery, kill-at-phase matrix on shard holders, "
        "per-shard mass-balance property test, shard-scoped matchmaking, "
        "control-plane snapshot deltas, OOM-sized model across a sharded "
        "zone, bytes-vs-K bench smoke) — in the default lane, and "
        "selectable on their own with -m sharding",
    )


def pytest_collection_modifyitems(config, items):
    """Slow (payload-scale) tests are OPT-IN: on the sandbox's single CPU
    core they are timing-sensitive under concurrent load, and the default
    sweep runs with -x where one contention flake aborts everything. Run
    them explicitly with `-m slow` or DVC_RUN_SLOW=1."""
    _mark_yardstick_pins_a_new_cell_moves(items)
    if os.environ.get("DVC_RUN_SLOW") or "slow" in (config.option.markexpr or ""):
        return
    skip = pytest.mark.skip(reason="slow: opt-in via -m slow or DVC_RUN_SLOW=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


# Two yardstick tests pin what a new cell must change, and are the benchmark's
# files, not a model_config PR's to edit (tests/yardstick/conftest.py, which
# marks the first of these for olmoe-1b-7b, is one of them too):
# - test_yardstick_manifest.py::test_configuration_file_is_what_the_program_runs
#   asserts ``reduced == []`` for every configuration; laguna-xs2 lists its cut;
# - test_yardstick_olmoe.py::test_manifest_holds_the_new_configuration_cell_and_metrics
#   asserts that olmoe-solo is the manifest's LAST cell, olmoe-1b-7b its last
#   configuration, OLMoE's five metrics its last five and olmoe-solo the last
#   name of four shared lists; PR 33 appended laguna-solo-8k after them.
# tests/yardstick/test_yardstick_laguna.py asserts what both asserted, with
# this cell's values; PR 35 appended smallthinker-solo-16k after laguna-solo-8k
# in turn (and to six of Laguna's lists), so that file's manifest test and the
# new configuration's case of the first are marked too, and
# tests/yardstick/test_yardstick_smallthinker.py asserts what they asserted,
# one place up. PR 37 (tracing) appended seven lifecycle.* metrics to per_layer
# and no cell: test_yardstick_smallthinker.py's manifest test, which asserts
# that SmallThinker's three END that list, is marked, and
# tests/yardstick/test_yardstick_lifecycle.py asserts what it asserted, seven
# places up. Strict and AssertionError only: each case fails loudly once it
# passes (the next benchmark PR relaxes the assertions and deletes this).
_YARDSTICK_PINS = (
    ("test_configuration_file_is_what_the_program_runs", "[laguna-xs2]",
     "asserts reduced == []; laguna-xs2 lists its cut (checked in test_yardstick_laguna.py)"),
    ("test_manifest_holds_the_new_configuration_cell_and_metrics", "test_yardstick_olmoe.py",
     "asserts that olmoe-solo ends the manifest; laguna-solo-8k was appended after it "
     "(checked in test_yardstick_laguna.py)"),
    ("test_configuration_file_is_what_the_program_runs", "[smallthinker-21b-a3b]",
     "asserts reduced == []; smallthinker-21b-a3b lists its cut (checked in test_yardstick_smallthinker.py)"),
    ("test_manifest_holds_the_new_configuration_cell_and_metrics", "test_yardstick_laguna.py",
     "asserts that laguna-solo-8k ends the manifest and is alone in Laguna's lists; smallthinker-solo-16k "
     "was appended after it (checked in test_yardstick_smallthinker.py)"),
    ("test_manifest_holds_the_new_configuration_cell_and_metrics", "test_yardstick_smallthinker.py",
     "asserts that SmallThinker's three metrics end per_layer; PR 37 appended the seven lifecycle.* metrics "
     "after them (checked, seven places up, in test_yardstick_lifecycle.py)"),
    # PR 39 (lfm2-24b-a2b, lfm2-solo-8k, conv.device_ms / conv.roofline / moe.bias_spread; the cell appended to
    # attention.device_ms's list): tests/yardstick/test_yardstick_lfm2.py asserts what each of these asserted,
    # three metrics, a cell and a configuration up.
    ("test_configuration_file_is_what_the_program_runs", "[lfm2-24b-a2b]",
     "asserts reduced == []; lfm2-24b-a2b lists its cut (checked in test_yardstick_lfm2.py)"),
    ("test_manifest_holds_the_seven_start_up_metrics_at_its_end", "test_yardstick_lifecycle.py",
     "asserts that the seven lifecycle.* metrics end per_layer, over six cells and five configurations; PR 39 "
     "appended three metrics, a cell and a configuration (checked in test_yardstick_lfm2.py)"),
    ("test_manifest_tail_as_the_smallthinker_test_asserted_it_seven_places_up", "test_yardstick_lifecycle.py",
     "asserts that smallthinker-solo-16k ends the cells and every appended list; lfm2-solo-8k was appended "
     "after it (checked in test_yardstick_lfm2.py)"),
    ("test_manifest_lists_the_metric_in_the_solo_cells", "test_yardstick_attention_metric.py",
     "asserts that attention.device_ms lists the two gpt2 cells alone; lfm2-solo-8k, whose only attention "
     "kernels are the full-causal ones it reads, was appended (checked in test_yardstick_lfm2.py)"),
    # PR 42 (glm-4.7-flash, glm47-flash-solo-8k, moe.chunks_extra; the cell appended to the lists LFM2's cell ended):
    # tests/yardstick/test_yardstick_glm4_moe_lite.py asserts what each of these asserted, one metric, a cell and a
    # configuration up.
    ("test_configuration_file_is_what_the_program_runs", "[glm-4.7-flash]",
     "asserts reduced == []; glm-4.7-flash lists its cut (checked in test_yardstick_glm4_moe_lite.py)"),
    ("test_manifest_holds_the_new_configuration_cell_and_metrics", "test_yardstick_lfm2.py",
     "asserts that lfm2-solo-8k ends the manifest, its three metrics per_layer and its lists; PR 42 appended a "
     "metric, a cell and a configuration (checked in test_yardstick_glm4_moe_lite.py)"),
    ("test_manifest_tail_as_the_lifecycle_tests_asserted_it_three_metrics_and_a_cell_up", "test_yardstick_lfm2.py",
     "asserts the manifest's tail three metrics and a cell up from the lifecycle metrics; glm47-flash-solo-8k and "
     "moe.chunks_extra were appended after them (checked in test_yardstick_glm4_moe_lite.py)"),
    # PR 48 (nemotron-3-nano-30b-a3b, nemotron3-nano-solo-8k, ssm.device_ms / ssm.roofline / ssm.carry_share; the cell
    # appended to the lists GLM's cell ended and to conv.* and moe.act_zero_share):
    # tests/yardstick/test_yardstick_nemotron_h.py asserts what each of these asserted, three metrics, a cell and a
    # configuration up.
    ("test_configuration_file_is_what_the_program_runs", "[nemotron-3-nano-30b-a3b]",
     "asserts reduced == []; nemotron-3-nano-30b-a3b lists its cut (checked in test_yardstick_nemotron_h.py)"),
    ("test_manifest_holds_the_new_configuration_cell_and_metric", "test_yardstick_glm4_moe_lite.py",
     "asserts that glm47-flash-solo-8k ends the manifest, moe.chunks_extra per_layer and its lists; PR 48 appended three "
     "metrics, a cell and a configuration (checked in test_yardstick_nemotron_h.py)"),
    ("test_manifest_tail_as_the_lfm2_tests_asserted_it_one_metric_and_a_cell_up", "test_yardstick_glm4_moe_lite.py",
     "asserts the manifest's tail one metric and a cell up from LFM2's; nemotron3-nano-solo-8k and the three ssm.* metrics "
     "were appended after them, and the cell to conv.* and moe.act_zero_share (checked in test_yardstick_nemotron_h.py)"),
    # PR 52 (kimi-linear-48b-a3b, kimi-linear-solo-8k, kda.device_ms / kda.roofline / kda.carry_share; the cell appended
    # to the lists Nemotron's cell ended but moe.act_zero_share and ssm.*):
    # tests/yardstick/test_yardstick_kimi_linear.py asserts what each of these asserted, three metrics, a cell and a
    # configuration up.
    ("test_configuration_file_is_what_the_program_runs", "[kimi-linear-48b-a3b]",
     "asserts reduced == []; kimi-linear-48b-a3b lists its cut (checked in test_yardstick_kimi_linear.py)"),
    ("test_manifest_holds_the_new_configuration_cell_and_metrics", "test_yardstick_nemotron_h.py",
     "asserts that nemotron3-nano-solo-8k ends the manifest, the three ssm.* metrics per_layer and its lists; PR 52 appended "
     "three metrics, a cell and a configuration (checked in test_yardstick_kimi_linear.py)"),
    ("test_manifest_tail_as_the_glm_tests_asserted_it_three_metrics_and_a_cell_up", "test_yardstick_nemotron_h.py",
     "asserts the manifest's tail three metrics and a cell up from GLM's; kimi-linear-solo-8k and the three kda.* metrics "
     "were appended after them (checked in test_yardstick_kimi_linear.py)"),
    # PR 56 (the nine scope.* metrics appended to per_layer; no cell, no configuration, no list touched):
    # tests/yardstick/test_yardstick_scopes.py runs both of these as they stand against the manifest nine places up.
    ("test_manifest_holds_the_new_configuration_cell_and_metrics", "test_yardstick_kimi_linear.py",
     "asserts that the three kda.* metrics end per_layer; PR 56 appended the nine scope.* metrics after them "
     "(checked, nine places up, in test_yardstick_scopes.py)"),
    ("test_manifest_tail_as_the_nemotron_tests_asserted_it_three_metrics_and_a_cell_up", "test_yardstick_kimi_linear.py",
     "asserts the manifest's tail three metrics up from Kimi's; the nine scope.* metrics were appended after them "
     "(checked, nine places up, in test_yardstick_scopes.py)"),
    # PR 57 (two device.collective_all_* metrics appended to per_layer; no cell, no configuration, no list touched):
    # tests/yardstick/test_yardstick_collective_pairs.py runs these as they stand against the manifest two places up.
    ("test_manifest_holds_the_nine_scope_metrics_at_its_end", "test_yardstick_scopes.py",
     "asserts that the nine scope.* metrics end per_layer, 66 entries; PR 57 appended device.collective_all_share and "
     "_exposed after them (checked, two places up, in test_yardstick_collective_pairs.py)"),
    ("test_manifest_as_the_kimi_tests_asserted_it_nine_places_up", "test_yardstick_scopes.py",
     "asserts Kimi's tail nine places up from the manifest's end; it is eleven now "
     "(checked, two places up, in test_yardstick_collective_pairs.py)"),
    # PR 60 (sdar-30b-a3b-chat, sdar-solo-4k, attention.bd_device_ms / bd_roofline / bd_tiles_share and
    # diffusion.head_rows_share; the cell appended to tok_s_chip's list and to twenty-three per-layer lists):
    # tests/yardstick/test_yardstick_sdar_moe.py asserts what each of these asserted, against the manifest less this
    # PR's entries.
    ("test_configuration_file_is_what_the_program_runs", "[sdar-30b-a3b-chat]",
     "asserts reduced == []; sdar-30b-a3b-chat lists its cut (checked in test_yardstick_sdar_moe.py)"),
    ("test_manifest_as_the_scope_tests_asserted_it_two_places_up", "test_yardstick_collective_pairs.py",
     "asserts that PR 57's two metrics end per_layer, 68 entries; PR 60 appended four metrics, a cell and a "
     "configuration (checked in test_yardstick_sdar_moe.py)"),
    # PR 64 (ouro-2.6b, ouro-solo-4k, recur.exit_entropy / expected_passes / outside_blocks_ms; the cell appended to
    # tok_s_chip's list and to twenty-one per-layer lists): tests/yardstick/test_yardstick_ouro.py asserts what each
    # of these asserted, against the manifest less this PR's entries.
    ("test_configuration_file_is_what_the_program_runs", "[ouro-2.6b]",
     "asserts reduced == []; ouro-2.6b lists its cut (checked in test_yardstick_ouro.py)"),
    ("test_manifest_holds_the_new_configuration_cell_and_metrics", "test_yardstick_sdar_moe.py",
     "asserts that sdar-solo-4k ends the manifest, SDAR's four metrics per_layer and its lists; PR 64 appended three "
     "metrics, a cell and a configuration (checked in test_yardstick_ouro.py)"),
    ("test_manifest_as_the_collective_pairs_tests_asserted_it_before_this_cell", "test_yardstick_sdar_moe.py",
     "takes SDAR's four metrics, cell and configuration off the manifest's END to run the older tail tests; PR 64's "
     "entries end it now (checked, with both PRs' entries taken off, in test_yardstick_ouro.py)"),
    # PR 67 (qwen3-next-80b-a3b, qwen3-next-solo-8k, gdn.device_ms / gdn.roofline / gdn.carry_share; the cell appended
    # to tok_s_chip's list and to twenty-nine per-layer lists): tests/yardstick/test_yardstick_qwen3_next.py asserts
    # what each of these asserted, against the manifest less this PR's entries, which it takes off BY NAME: its own
    # manifest test pins no position, so the next PR's entries move nothing there.
    ("test_configuration_file_is_what_the_program_runs", "[qwen3-next-80b-a3b]",
     "asserts reduced == []; qwen3-next-80b-a3b lists its cut (checked in test_yardstick_qwen3_next.py)"),
    ("test_manifest_holds_the_new_configuration_cell_and_metrics", "test_yardstick_ouro.py",
     "asserts that ouro-solo-4k ends the manifest, Ouro's three metrics per_layer and its lists; PR 67 appended three "
     "metrics, a cell and a configuration (checked in test_yardstick_qwen3_next.py)"),
    ("test_manifest_as_the_sdar_tests_asserted_it_before_this_cell", "test_yardstick_ouro.py",
     "takes Ouro's three metrics, cell and configuration off the manifest's END to run the older tail tests; PR 67's "
     "entries end it now, and what it takes off instead leaves lists that name a cell the view no longer has: a "
     "ManifestError from check() (checked, with this PR's entries taken off by name, in test_yardstick_qwen3_next.py)",
     ValueError),
    # PR 70 (xing4.0-29b-a4b, xing4-solo, scope.residual_ms / hc.roofline / hc.res_offdiag; the cell appended to
    # tok_s_chip's list and to the twenty-eight per-layer lists that carry glm47-flash-solo-8k):
    # tests/yardstick/test_yardstick_xing4.py asserts what each of these asserted, against the manifest less PR 67's
    # and this PR's entries, taken off BY NAME; its own manifest test pins no position.
    ("test_configuration_file_is_what_the_program_runs", "[xing4.0-29b-a4b]",
     "asserts reduced == []; xing4.0-29b-a4b lists its cut (checked, and the older test run with the list emptied, in "
     "test_yardstick_xing4.py)"),
    ("test_manifest_as_the_ouro_tests_asserted_it_before_this_cell", "test_yardstick_qwen3_next.py",
     "runs Ouro's manifest cases against the manifest less PR 67's entries, which no longer ends on Ouro's: PR 70's "
     "entries follow (the same five cases against the manifest less both PRs' entries, in test_yardstick_xing4.py)",
     ValueError),
    # PR 72 (tracing: six loop.* metrics of the program's own timeline of the chip's queue appended to per_layer; no
    # cell, no configuration, no list touched; loop.wait_share and loop.step_wall_max_over_median list the thirteen
    # solo cells): tests/yardstick/test_yardstick_chip_wait.py runs each of these as it stands against the manifest
    # less this PR's entries, taken off BY NAME; its own manifest test pins no position.
    ("test_manifest_holds_the_new_configuration_cell_and_metrics_by_name", "test_yardstick_xing4.py",
     "asserts the exact set of per-layer metrics xing4-solo reports; loop.wait_share and "
     "loop.step_wall_max_over_median list it now (checked in test_yardstick_chip_wait.py)"),
    ("test_manifest_holds_the_new_configuration_cell_and_metrics_by_name", "test_yardstick_qwen3_next.py",
     "asserts the exact set of per-layer metrics qwen3-next-solo-8k reports; loop.wait_share and "
     "loop.step_wall_max_over_median list it now (checked in test_yardstick_chip_wait.py)"),
    ("test_manifest_as_the_qwen3_next_tests_asserted_it_before_this_cell", "test_yardstick_xing4.py",
     "runs Ouro's manifest cases against the manifest less PR 67's and PR 70's entries, which no longer ends on "
     "Ouro's: PR 72's six metrics follow (the same five cases against the manifest less all three PRs' entries, in "
     "test_yardstick_chip_wait.py)",
     ValueError),
)


def _mark_yardstick_pins_a_new_cell_moves(items):
    for item in items:
        for test, where, reason, *also in _YARDSTICK_PINS:   # ``also``: what the pin raises besides an AssertionError
            if getattr(item, "originalname", None) == test and where in item.nodeid:
                item.add_marker(pytest.mark.xfail(reason=reason, raises=(AssertionError, *also), strict=True))


# Every program jax compiles holds memory maps of its own until its cache drops it, and a test that runs a model
# eagerly compiles hundreds (tests/test_kda.py: 600 maps a test). The kernel gives a process 65,530
# (vm.max_map_count); a worker that had run enough such files ended in the compiler with a segmentation fault or an
# abort, in whichever test came next (PR 52: three whole runs of four). Dropping the caches gives the maps back.
_MAPS_HIGH = 40_000


@pytest.fixture(autouse=True)
def _memory_maps_stay_under_the_kernels_limit():
    yield
    try:
        with open("/proc/self/maps") as fh:
            held = sum(1 for _ in fh)
    except OSError:
        return
    if held > _MAPS_HIGH:
        import jax

        jax.clear_caches()


@pytest.fixture(scope="session")
def rng():
    import jax

    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs


@pytest.fixture
def np_rng():
    return np.random.default_rng(0)
