"""The check's control and faults: a run of the harness at a tiny size on
the CPU (the look for a chip skipped), with the timed path broken
underneath, must come out not correct; the sound run must come out
correct."""
import dataclasses
import time

import jax.numpy as jnp
import pytest

from bench import control, harness, rehearse

SEED = 2 ** 31 + 17


def _correct(workload="sift1m-ivf1024.batch", adjust=rehearse.shrink) -> bool:
    rc = harness.run_cell(workload, SEED, 1.0, False,
                          t_start=time.perf_counter(), rehearsal=True,
                          adjust=adjust)
    return rc == 0


def test_sound_run_is_correct():
    assert _correct()


def test_control_sq8_store_is_not_correct():
    """The program's own lower-precision path: an int8 (SQ8) store where
    the configuration states float32 with bfloat16 products."""
    assert not _correct(adjust=lambda cfg, mix: control.lower(
        *rehearse.shrink(cfg, mix)))


def _altered(orig):
    def step(index, s):       # every id the probe produces is off by one
        s = orig(index, s)
        return dataclasses.replace(
            s, topk_i=jnp.where(s.topk_i >= 0, s.topk_i + 1, s.topk_i))
    return step


def _unchanged(orig):
    def step(index, s):       # the step returns its state as it came
        return s
    return step


@pytest.mark.parametrize("make", [_altered, _unchanged],
                         ids=["answer_altered", "state_unchanged"])
def test_broken_probe_step_is_not_correct(monkeypatch, make):
    from repro.index import ivf
    monkeypatch.setattr(ivf, "probe_step", make(ivf.probe_step))
    assert not _correct()


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    from repro.serve import DarthServer
    orig = DarthServer.serve

    def half(self, queries, r_targets, **kw):
        h = (queries.shape[0] + 1) // 2
        results, stats = orig(self, queries[:h], r_targets[:h], **kw)
        return results + [None] * (queries.shape[0] - h), stats
    monkeypatch.setattr(DarthServer, "serve", half)
    assert not _correct()
