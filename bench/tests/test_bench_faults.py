"""``correct`` comes out false for the control and for each fault the
cells can have: an answer altered where it is produced, and half of the
rows left out."""

import numpy as np
import pytest

from bench import control, generate, spec
from bench.tests.helpers import benchmark_with_lookup, run_tiny


@pytest.mark.parametrize("workload", ["laion-quality-scan",
                                      "criteo-lookup-zipf"])
def test_control_one_precision_lower_is_not_correct(workload):
    cfg = generate.load_config(spec.config_file(
        spec.cell(benchmark_with_lookup(), workload)["config"]))
    quant = control.control_quant(cfg)
    assert quant and set(quant.values()) == {"fp8_e4m3"}
    res = run_tiny(workload, quant=quant)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def _patch_mask(monkeypatch, change):
    from repro.dataset import executor
    real = executor.eval_mask

    def eval_mask(pred, tbl, use_kernel):
        return change(np.array(real(pred, tbl, use_kernel), bool))

    monkeypatch.setattr(executor, "eval_mask", eval_mask)


def _flip_first(mask):
    mask[0] = ~mask[0]
    return mask


def _keep_half(mask):
    mask[1::2] = False
    return mask


@pytest.mark.parametrize("fault", [_flip_first, _keep_half],
                         ids=["answer_altered", "half_left_out"])
def test_fault_in_the_timed_path_is_not_correct(monkeypatch, fault):
    _patch_mask(monkeypatch, fault)
    res = run_tiny("laion-quality-scan")
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0
