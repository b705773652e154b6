"""The comparison that decides ``correct``, driven through a whole run on
the CPU at a size a test can hold: a sound run passes; the fp8 control
reads above the limit; a run whose served path is broken underneath
comes out not correct.

The tiny configuration's limit (``tiny.json``) sits between the widest
gap sound runs read here (under 0.005) and the control's (above 0.02).
"""
import numpy as np
import pytest

import cell
import common

CFG = common.load_json(common.HERE / "tests" / "tiny.json")
MIX = common.load_json(common.HERE / "tests" / "tiny-mix.json")
SEED = 3_000_000_019


def _run(fault=None, control=False, seed=SEED):
    return cell.run("tiny.test", seed, 2.0, False, cfg=CFG, mix=MIX,
                    require_tpu=False, control=control, fault=fault)


def _alter_a_token(engine):
    """A token altered where it is produced: each decode call serves, for
    its first row, the token after the argmax."""
    runner = engine.runner
    decode = runner.decode

    def altered(*a, **k):
        logits = np.array(decode(*a, **k), np.float32)
        logits[0] = np.roll(logits[0], 1)
        return logits

    runner.decode = altered


def _state_unchanged(engine):
    """A step that returns its state unchanged: decode writes no KV."""
    runner = engine.runner
    step = runner._decode_jit

    def unchanged(params, tokens, block_tables, pos, pages):
        logits, _ = step(params, tokens, block_tables, pos, pages)
        return logits, pages

    runner._decode_jit = unchanged


@pytest.fixture(scope="module")
def sound():
    return _run(control=True)


def test_sound_run_is_correct(sound):
    checks = sound["checks"]
    assert checks["compared_tokens"]["value"] >= 40
    assert sound["correct"] or checks["backlog_left"]["value"] == 0
    assert checks["logit_gap"]["value"] <= checks["logit_gap"]["limit"]
    assert list(sound)[-1] == "checks"


def test_control_fails_the_limit(sound):
    """The control, judged in the program's place by the same verdict,
    comes out not correct."""
    limit = CFG["correct"]["logit_gap_limit"]
    assert max(sound["extra"]["control_gaps"]) > limit
    assert max(sound["extra"]["gaps"]) <= limit
    assert sound["extra"]["control_correct"] is False
    checks = sound["extra"]["control_checks"]
    assert checks["logit_gap"]["value"] > checks["logit_gap"]["limit"]


@pytest.mark.parametrize("fault", [_alter_a_token, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_path_is_not_correct(fault):
    r = _run(fault=fault)
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > \
        r["checks"]["logit_gap"]["limit"]
