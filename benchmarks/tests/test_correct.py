"""`correct` comes out false where it should: the control (the reference in
the nearest precision below the configuration's, put in the program's place)
and each fault a cell can have, planted underneath the timed path. These skip
the harness's look for a chip (the rehearsal flag) and drive the rest of a run
at a size a test run can hold. The limits are this tiny size's own
(`rehearsal_tiny.json`), read on the CPU, where the program's float32 is exact;
the cells' limits, read on the chip at the cells' sizes, are in
benchmarks/limits/."""
import argparse
import json
import os
import time

import numpy as np
import pytest
from conftest import HERE, ROOT

import harness
import serve_driver
import train_driver

def drive(cell_name, driver, seed=11, seconds=2.0, control=0):
    manifest = harness.load_manifest()
    cell = harness.find_cell(manifest, cell_name)
    with open(os.path.join(HERE, "rehearsal_tiny.json")) as f:
        over = json.load(f)
    import run as run_module

    cfg = run_module._merge(harness.load_config(manifest, cell["config"]), over["config"])
    mix = run_module._merge(harness.load_traffic(cell["traffic"]), over["traffic"])
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0, control=control)
    devices = harness.require_chips(1, rehearsal=True)
    limits = run_module._merge(harness.load_limits(cell_name), over["limits"])
    family = harness.load_family(cfg, needs=driver.NEEDS)
    return driver.run(manifest=manifest, cell=cell, cfg=cfg, family=family,
                      mix=mix, limits=limits, args=args, devices=devices,
                      t_process_start=time.perf_counter())


def test_serve_sound_run_is_correct_and_control_is_not():
    result = drive("galactica-1.3b.serve-chat", serve_driver, control=1)
    checks = result["checks"]
    assert result["correct"] is True and result["failed"] == 0
    limit = checks["token_gap_max"]["limit"]
    assert checks["token_gap_max"]["value"] <= limit
    # float8 operands in the program's place, through the harness's own judge:
    # the token they put first lies further below the reference's best than
    # the limit allows
    control = result["stand_ins"]["control"]
    assert control["correct"] is False
    assert control["checks"]["token_gap_max"]["limit"] == limit
    assert control["checks"]["token_gap_max"]["value"] > 3 * limit


def test_serve_altered_token_is_not_correct(monkeypatch):
    from distributeddeeplearning_tpu.serve.engine import PagedInferenceEngine

    sound = PagedInferenceEngine.decode

    calls = [0]

    def altered(self, tokens, pos):
        out = np.array(sound(self, tokens, pos))
        calls[0] += 1
        if calls[0] % 4 == 0:  # tokens altered where they are produced
            out[:] = (out + 1) % self.vocab_size
        return out

    monkeypatch.setattr(PagedInferenceEngine, "decode", altered)
    result = drive("galactica-1.3b.serve-chat", serve_driver)
    assert result["correct"] is False
    assert result["checks"]["token_gap_max"]["value"] > result["checks"]["token_gap_max"]["limit"]


def _broken_build(monkeypatch, wrap):
    family = harness.load_family({"family": "opt"})
    sound = family.build_train

    def build_train(cfg, job, devices, params):
        mesh, step, state = sound(cfg, job, devices, params)
        return mesh, wrap(step), state

    monkeypatch.setattr(family, "build_train", build_train)


def test_train_sound_run_is_correct_and_control_is_not():
    result = drive("galactica-125m.train-2k", train_driver, control=1)
    checks = result["checks"]
    assert result["correct"] is True, checks
    # the reference put in the program's place (float8 operands; half of the
    # batch left out) goes through the harness's own judge under the program's
    # names and limits, and each comes out not correct
    for label in ("control", "halfbatch"):
        stood = result["stand_ins"][label]
        assert stood["correct"] is False, stood
        assert any(e["limit"] is not None and e["value"] > e["limit"]
                   for e in stood["checks"].values()), stood
        assert all(stood["checks"][n]["limit"] == checks[n]["limit"]
                   for n in stood["checks"])


def test_train_state_returned_unchanged_is_not_correct(monkeypatch):
    def wrap(step):
        def unchanged(state, batch):
            import jax

            _, metrics = step(jax.tree_util.tree_map(lambda x: x.copy(), state), batch)
            return state, metrics
        return unchanged

    _broken_build(monkeypatch, wrap)
    result = drive("galactica-125m.train-2k", train_driver)
    assert result["correct"] is False
    assert result["checks"]["grad_norm_gap"]["value"] == pytest.approx(1.0)
    assert result["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    def wrap(step):
        def half(state, batch):
            import jax.numpy as jnp

            # the mean is taken over the first half of the rows only
            cut = {k: jnp.concatenate([v[: v.shape[0] // 2]] * 2) for k, v in batch.items()}
            return step(state, cut)
        return half

    _broken_build(monkeypatch, wrap)
    result = drive("galactica-125m.train-2k", train_driver)
    assert result["correct"] is False
    gap = result["checks"]["grad_norm_gap"]
    assert gap["value"] > 10 * gap["limit"]
