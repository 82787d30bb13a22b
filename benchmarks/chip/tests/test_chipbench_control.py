"""The control comes out not correct: the plain reference computed one
precision below the configurations' bfloat16 (float8 products, float8
residual stream), put in the program's place, at the small size."""

import jax
import pytest

from _util import SMALL_LIMITS, small_files
from chipbench import check, reference, spec
from chipbench import state as S


@pytest.mark.parametrize("seed", [20261017, 3000000001])
def test_control_fails_a_limit(seed):
    from repro.models.config import ModelConfig
    from repro.train.steps import init_train_state
    _, (entry, config, traffic) = small_files("stablelm-3b-8l.save_paced")
    m = dict(config["model"])
    m["block_pattern"] = tuple(m.get("block_pattern") or ())
    cfg = ModelConfig(**m)
    shape = jax.eval_shape(
        lambda: init_train_state(jax.random.key(0), cfg))["params"]
    t = config["train"]
    batches = [S.batch_at(seed, k, t["batch"], t["seq_len"],
                          m["vocab_size"], traffic["zipf_a"])
               for k in (1, 2, 3)]
    ref = reference.run_reference(config, seed, shape, batches)
    ctl = reference.run_reference(config, seed, shape, batches,
                                  precision="fp8")
    gaps = check.training_gaps(ctl, ref)
    failed = [k for k in SMALL_LIMITS if gaps[k] > SMALL_LIMITS[k]]
    assert failed, gaps
    assert check.limit_checks(gaps, SMALL_LIMITS).correct is False


def test_calibrate_judges_at_the_cells_limits():
    """calibrate.py's verdicts go through ``Checks`` at the configuration's
    own limits: the program's largest readings come out correct; the
    control's and the half-batch fault's smallest loss gaps, with the
    program's median gaps beside them, come out not correct."""
    import calibrate
    config = spec.load_cell(spec.load_benchmark(),
                            "stablelm-3b-8l.save_paced")[1]
    program = {"loss_gap": 0.00247, "grad_median_gap": 0.0222,
               "update_median_gap": 0.0082}
    out = {"program": program,
           "control": dict(program, loss_gap=0.00687),
           "half_batch": dict(program, loss_gap=0.00719)}
    assert calibrate.judge(config["limits"], out) == calibrate.WANT
    out["control"] = dict(program)       # a control as close as the program
    assert calibrate.judge(config["limits"], out)["control"] is True
