"""The comparison that decides `correct` fails what it must.

Each case skips the look for a chip, drives a whole run at a tiny size
with the timed path broken underneath (or the control in its place),
and must read `correct: false`:

* an answer altered where it is produced (the Leader's share of one key
  per batch; one leaf of a full-domain output);
* the control: the Leader's degraded path (its share alone), and
  full-domain keys of the next narrower value type (uint32 for uint64).
"""

import numpy as np
import pytest

import run

TINY = {
    "served": ("dense_pir_1m_256b", "served_poisson",
               {"config": {"sizes": {"num_records": 4096}},
                "traffic": {"max_batch_size": 4, "pool": 32,
                            "client_threads": 16,
                            "arrivals": {"rate_qps": 20}}}),
    "dpf": ("dpf_full_domain_ld20_u64", "full_domain_stream",
            {"config": {"sizes": {"log_domain": 10}},
             "traffic": {"pool": 2}}),
}


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax_cache"))


def _run(kind, cache_dir, control=False):
    config, traffic, overrides = TINY[kind]
    return run.run_cell(
        config, traffic, seed=2**31 + 5, seconds=1.0, trace=False,
        metric_entries=[], require_tpu=False, overrides=overrides,
        control=control, cache_dir=cache_dir,
    )


@pytest.mark.parametrize("kind", ["served", "dpf"])
def test_sound_run_is_correct(kind, cache_dir):
    assert _run(kind, cache_dir)["correct"]


@pytest.mark.parametrize("kind", ["served", "dpf"])
def test_control_is_not_correct(kind, cache_dir):
    result = _run(kind, cache_dir, control=True)
    assert not result["correct"]
    assert sum(c["value"] for c in result["checks"].values()) > 0


def test_altered_answer_is_caught(cache_dir, monkeypatch):
    from distributed_point_functions_tpu.serving import LeaderSession

    original = LeaderSession._evaluate_keys

    def altered(self, keys):
        out = list(original(self, keys))
        out[0] = bytes([out[0][0] ^ 1]) + out[0][1:]
        return out

    monkeypatch.setattr(LeaderSession, "_evaluate_keys", altered)
    result = _run("served", cache_dir)
    assert not result["correct"]
    assert result["checks"]["wrong_answers"]["value"] > 0


def test_altered_leaf_is_caught(cache_dir, monkeypatch):
    import jax.numpy as jnp

    from distributed_point_functions_tpu.dpf import DistributedPointFunction

    original = DistributedPointFunction.evaluate_next

    def altered(self, prefixes, ctx):
        out = original(self, prefixes, ctx)
        return out.at[3].add(jnp.ones_like(out[3]))

    monkeypatch.setattr(DistributedPointFunction, "evaluate_next", altered)
    result = _run("dpf", cache_dir)
    assert not result["correct"]
    assert result["checks"]["wrong_leaves"]["value"] > 0


def test_refusal_at_admission_is_a_failure_not_a_wrong_answer(
        cache_dir, monkeypatch):
    # A full queue refuses requests (`Overloaded`): they count in
    # `failed` and leave `correct` alone; any other failure does not.
    from distributed_point_functions_tpu.serving import LeaderSession
    from distributed_point_functions_tpu.serving.batcher import Overloaded

    original = LeaderSession.handle_request
    calls = {"n": 0}

    def refusing(self, request, *args, **kwargs):
        calls["n"] += 1
        if calls["n"] > 32 and calls["n"] % 4 == 0:  # past the warm-up
            raise Overloaded("queue full")
        return original(self, request, *args, **kwargs)

    monkeypatch.setattr(LeaderSession, "handle_request", refusing)
    result = _run("served", cache_dir)
    assert result["failed"] > 0
    assert result["correct"], result["checks"]
    assert result["checks"]["unanswered"]["value"] == 0
