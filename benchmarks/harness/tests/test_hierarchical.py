"""The hierarchical DPF cell end to end on the CPU at a tiny size: the
sound run is correct with every output checked, the control (a narrower
value type) is not, the traced run's counters read no frontier readback
and no per-leaf gather exit, and a program without the array-prefix path
is refused before any evaluation."""

import pytest

import cell
import run

TINY = {
    "config": {"sizes": {"log_domains": [5, 7, 9, 11, 12],
                         "nonzeros": 128}},
    "traffic": {"pool": 2},
}


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax_cache"))


def _run(cache_dir, control=False, trace=False, entries=()):
    return run.run_cell(
        "dpf_hier_ld32_u32_nz2p20", "hierarchical_stream", seed=2**33 + 26,
        seconds=1.0, trace=trace, metric_entries=list(entries),
        require_tpu=False, overrides=TINY, control=control,
        cache_dir=cache_dir,
    )


def test_sound_run_is_correct(cache_dir):
    result = _run(cache_dir, entries=[
        {"name": "ns_per_leaf", "unit": "ns/leaf"},
        {"name": "setup_s", "unit": "s"},
    ])
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 4 and result["failed"] == 0
    assert result["checks"]["unchecked_pairs"]["value"] == 0
    assert result["metrics"]["ns_per_leaf"]["value"] > 0


def test_control_is_not_correct(cache_dir):
    result = _run(cache_dir, control=True)
    assert not result["correct"]
    assert result["checks"]["wrong_outputs"]["value"] > 0


def test_traced_run_reads_the_counters(cache_dir):
    # On the CPU the trace has no TPU plane, so the span metrics read
    # nothing there; the traced part's counter deltas come back.
    entries = cell.cell_metrics(cell.benchmark(), "hier32.uniform",
                                "per_layer")
    result = _run(cache_dir, trace=True, entries=entries)
    assert result["correct"], result["checks"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics == {"readback_bytes_per_eval.hier": 0,
                       "gather_exit_leaves_per_eval.hier": 0}


def test_span_metrics_read_the_resumed_levels(cache_dir):
    from distributed_point_functions_tpu.observability import tracing

    traffic = cell.traffic("hierarchical_stream")
    driver = cell.driver(traffic["driver"]).Driver(
        run._merge(cell.config("dpf_hier_ld32_u32_nz2p20"), TINY["config"]),
        run._merge(traffic, TINY["traffic"]), 7, run.log,
    )
    run.use_compile_cache(cache_dir)
    driver.setup()
    tracing.span_log.start()
    try:
        driver.warm()
    finally:
        record = {"spans": tracing.span_log.drain()}
    levels = [(r["attrs"] or {}).get("level")
              for r in record["spans"]["records"]
              if r["name"] == "dpf.evaluate_until"]
    assert levels == [0, 1, 2, 3, 4] * 2
    for name in ("level_call_ms.hier", "prefix_host_ms.hier"):
        assert cell.metric(name).read(record) > 0


def test_program_without_array_prefixes_is_refused(cache_dir, monkeypatch):
    from distributed_point_functions_tpu import dpf

    monkeypatch.delattr(dpf, "DevicePartialEvaluations")
    with pytest.raises(RuntimeError, match="array-prefix"):
        _run(cache_dir)
