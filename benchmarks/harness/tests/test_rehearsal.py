"""CPU rehearsal: every driver parameter runs end to end at a tiny size.

Each case loads its configuration and traffic files by name, as the
cells of BENCHMARK.json and the ones PERF.md keeps for later do, and
only shrinks sizes and rates: a later cell needs data files only. The
run skips the look for a chip and goes through set-up, warm-up, the
window, the reference check and the metric reducers.
"""

import os
import subprocess
import sys

import pytest

import cell
import run

TINY_PIR = {"sizes": {"num_records": 4096, "record_bytes": 256}}
TINY_TRAFFIC = {"max_batch_size": 4, "pool": 64, "client_threads": 32}
E2E = [
    {"name": "p50_ms", "unit": "ms"},
    {"name": "p95_ms", "unit": "ms"},
    {"name": "qps", "unit": "queries/s"},
    {"name": "setup_s", "unit": "s"},
    {"name": "helper_leg_p50_ms.served", "unit": "ms"},
    {"name": "batch_keys_mean.served", "unit": "keys"},
]

CASES = {
    "leader_helper.poisson": ("dense_pir_1m_256b", "served_poisson",
                              {"arrivals": {"rate_qps": 20}}),
    "leader_helper.bursty": ("dense_pir_1m_256b", "served_bursty",
                             {"arrivals": {"rate_qps": 10, "burst_size": 4,
                                           "burst_interval_s": 0.5}}),
    "leader_helper.above_knee": ("dense_pir_1m_256b", "served_above_knee",
                                 {"arrivals": {"rate_qps": 30}}),
    "plain.closed": ("dense_pir_1m_256b", "plain_closed256",
                     {"arrivals": {"clients": 6}}),
    "plain.closed8": ("dense_pir_1m_256b", "plain_closed8", {}),
    "mesh4x1.plain.closed": ("dense_pir_4m_256b_mesh4x1", "plain_closed256",
                             {"arrivals": {"clients": 4},
                              "max_batch_size": 2}),
}


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax_cache"))


@pytest.mark.parametrize("case", sorted(CASES))
def test_served_driver_parameters(case, cache_dir):
    config, traffic, extra = CASES[case]
    result = run.run_cell(
        config, traffic, seed=2**33 + 7, seconds=1.5, trace=False,
        metric_entries=E2E, require_tpu=False, cache_dir=cache_dir,
        overrides={"config": TINY_PIR,
                   "traffic": run._merge(TINY_TRAFFIC, extra)},
    )
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"p50_ms", "p95_ms", "qps", "setup_s"} <= set(result["metrics"])
    # The Leader/Helper metrics read nothing in a plain cell.
    leader_helper = cell.traffic(traffic)["session"] == "leader_helper"
    for name in ("helper_leg_p50_ms.served", "batch_keys_mean.served"):
        assert (name in result["metrics"]) == leader_helper
    assert list(result)[-1] == "checks"


def test_traced_run_stops_its_trace_and_keeps_the_window(cache_dir):
    # On the CPU the trace has no TPU plane, so the trace-based metrics
    # read nothing; the timer must still stop the profiler mid-window
    # and the counters of the traced part must come back.
    entries = cell.cell_metrics(cell.benchmark(), "pir1m.served.poisson",
                                "per_layer")
    result = run.run_cell(
        "dense_pir_1m_256b", "served_poisson", seed=5, seconds=1.5,
        trace=True, metric_entries=entries, require_tpu=False,
        cache_dir=cache_dir,
        overrides={"config": TINY_PIR,
                   "traffic": run._merge(TINY_TRAFFIC,
                                         {"arrivals": {"rate_qps": 20}})},
    )
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {
        "helper_leg_p50_ms.served", "batch_keys_mean.served"
    }


def test_full_domain_driver(cache_dir):
    result = run.run_cell(
        "dpf_full_domain_ld20_u64", "full_domain_stream", seed=99,
        seconds=1.0, trace=False,
        metric_entries=[{"name": "ns_per_leaf", "unit": "ns/leaf"}],
        require_tpu=False, cache_dir=cache_dir,
        overrides={"config": {"sizes": {"log_domain": 10}},
                   "traffic": {"pool": 3}},
    )
    assert result["correct"], result["checks"]
    assert result["metrics"]["ns_per_leaf"]["value"] > 0


def test_every_benchmark_cell_names_existing_files():
    bench = cell.benchmark()
    for entry in bench["workloads"]:
        traffic = cell.traffic(entry["traffic"])
        cell.config(entry["config"])
        cell.driver(traffic["driver"])
    for section in ("end_to_end", "per_layer"):
        for metric in bench[section]:
            assert callable(cell.metric(metric["name"]).read)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(cell.UnknownDevice):
        cell.peaks("TPU v0 imaginary")
    assert cell.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_command_without_a_chip_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(cell.HARNESS_DIR, "run.py"),
         "--workload", "pir1m.served.poisson", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode == run.NO_CHIP
    assert proc.stdout.strip() == ""
