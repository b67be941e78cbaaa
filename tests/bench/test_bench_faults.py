"""Whole runs of the harness on the CPU, at a size the CPU holds: the look
for a chip is skipped, the rest of a run (set-up, window, check) is the
one the chip runs.  A sound program comes out ``correct``; the timed path
broken underneath, once for each fault a serving cell can have, does not.
"""
import pytest

from bench.faults import FAULTS
from bench.harness import Run, judge

SEED = 2 ** 32 + 5


def _run(root, workload, hook=None, trace=False):
    run = Run(root, workload, SEED, 1.5, trace, require_chip=False,
              engine_hook=hook, log=lambda s: None)
    return run, run.execute()


@pytest.mark.parametrize("workload", ["tiny.longgen", "tiny.code"])
def test_sound_run_is_correct(tiny_root, workload):
    run, res = _run(tiny_root, workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert run.checked_tokens >= 50
    assert res["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) >= {"setup_s", "tbt_p95_ms"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(tiny_root, fault):
    _, res = _run(tiny_root, "tiny.longgen", FAULTS[fault])
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_control_in_the_programs_place_is_not_correct(tiny_root):
    """The control's tokens, judged by the run's own checks and limits."""
    run = Run(tiny_root, "tiny.longgen", SEED, 1.5, False,
              require_chip=False, log=lambda s: None)
    res = run.execute(control=True)
    assert res["correct"], res["checks"]
    assert not judge(run.control), run.control
    assert run.control["logit_gap"]["limit"] == \
        res["checks"]["logit_gap"]["limit"]


def test_traced_run_reports_per_layer_metrics_only(tiny_root):
    """On the CPU the trace has no TPU plane: the device metrics find
    nothing to read and are left out, never reported as 0."""
    run, res = _run(tiny_root, "tiny.longgen", trace=True)
    assert res["correct"]
    assert "setup_s" not in res["metrics"]
    assert set(res["metrics"]) == {"batch_occupancy.longgen"}
    assert 0 < res["metrics"]["batch_occupancy.longgen"]["value"] <= 100
    assert res["device"]["busy_s"] == 0 and res["device"]["window_s"] > 0
    assert len(run.decode_calls) > 0 and len(run.prefill_calls) > 0
