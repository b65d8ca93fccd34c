# One-command entries for the TPU-native DPF framework.
#
# The reference drives everything through Bazel + .bazelci/presubmit.yml;
# here the equivalents are pytest (hermetic CPU, 8 virtual devices),
# protoc codegen, the native C++ oracle build, and the benchmark suites.

PY ?= python

.PHONY: test test-fast protos native bench smoke sweeps dryrun lint ci

test:          ## full hermetic suite (CPU, virtual 8-device mesh)
	$(PY) -m pytest tests/ -q

test-fast:     ## ~8 min hermetic signal incl. core invariants + tiny Pallas
	$(PY) -m pytest tests/test_aes.py tests/test_aes_sbox_tower.py \
	    tests/test_proto_validator.py tests/test_hybrid_crypto.py \
	    tests/test_serialization.py tests/test_farm_hash.py \
	    tests/test_native.py tests/test_native_cuckoo.py \
	    tests/test_testing_utils.py tests/test_demo.py \
	    tests/test_core_fast.py \
	    tests/test_serving_batcher.py tests/test_serving_transport.py \
	    tests/test_serving_service.py tests/test_observability.py \
	    tests/test_device_observability.py tests/test_slo.py \
	    tests/test_phase_recorder.py tests/test_transfer_ledger.py \
	    tests/test_critical_path.py \
	    tests/test_autoprofile.py \
	    tests/test_events.py tests/test_debug_bundle.py \
	    tests/test_prober.py \
	    tests/test_regression_gate.py \
	    tests/test_robustness.py tests/test_chaos.py \
	    tests/test_snapshots.py \
	    tests/test_pipelined_staging.py tests/test_pipelined_batcher.py \
	    tests/test_capacity.py tests/test_overload.py \
	    tests/test_heavy_hitters.py tests/test_incremental_reuse.py \
	    tests/test_mesh_serving.py \
	    tests/test_fleet.py tests/test_fleet_rotation.py \
	    tests/test_fleet_consistency.py \
	    tests/test_federation.py tests/test_fleet_telemetry.py \
	    tests/test_single_device_donation.py \
	    tests/test_sparse_degraded.py \
	    tests/test_pallas_fast.py -q

protos:        ## regenerate *_pb2.py from protos/*.proto
	cd protos && ./generate.sh

native:        ## build the C++ oracle kernels (ctypes-loaded)
	cd native && ./build.sh

bench:         ## headline benchmark (TPU only; emits one JSON line)
	$(PY) bench.py

smoke:         ## served-path bring-up check on one chip
	$(PY) chip_smoke.py

sweeps:        ## reference-mirroring benchmark sweeps (small shapes)
	$(PY) benchmarks/run_benchmarks.py

dryrun:        ## driver-style multichip dryrun on 8 virtual CPU devices
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	    $(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

lint:          ## stdlib AST lint (no flake8/ruff in this image)
	$(PY) tools/lint.py

ci:            ## presubmit: lint + protoc-check + native + test-fast + dryrun
	bash ci/presubmit.sh
