"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for the chip and drives the rest of a
run (``run.execute``) on the CPU at a tiny size, with one fault planted in
the program: a step that returns its state unchanged, half of the batch
left out with the mean taken over the rest, a token or an answer altered
where it is produced, and training losses that turn non-finite in the
window.  These cells run on one chip, so there is no exchange between
chips to leave out.
"""

import jax
import pytest

import run as R


def _execute(cell):
    line, out = R.execute(cell, 2 ** 31 + 11, 0.3, False, jax.devices()[:1])
    return line


@pytest.mark.parametrize("name", ["tiny2.train", "tiny3.train",
                                  "tiny.whatif"])
def test_sound_run_is_correct(name, tiny_cell):
    line = _execute(tiny_cell(name))
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"


def _wrap_step(monkeypatch, wrap):
    from repro.core import distributed
    real = distributed.make_train_step
    monkeypatch.setattr(distributed, "make_train_step",
                        lambda run, loss_fn, engine="sequential":
                        wrap(real(run, loss_fn, engine=engine)))


def test_train_state_unchanged(monkeypatch, tiny_cell):
    def wrap(step):
        def broken(params, opt, batch):
            return params, opt, step(params, opt, batch)[2]
        return broken
    _wrap_step(monkeypatch, wrap)
    assert not _execute(tiny_cell("tiny2.train"))["correct"]


def test_train_half_batch(monkeypatch, tiny_cell):
    def wrap(step):
        def broken(params, opt, batch):
            half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
            return step(params, opt, half)
        return broken
    _wrap_step(monkeypatch, wrap)
    assert not _execute(tiny_cell("tiny2.train"))["correct"]


def test_train_token_altered(monkeypatch, tiny_cell):
    from repro.data import pipeline
    real = pipeline.make_batch_fn

    def make(cfg, batch, seq, seed=0):
        fn = real(cfg, batch, seq, seed=seed)

        def broken(step):
            b = dict(fn(step))
            labels = b["labels"].copy()
            labels[0] = (labels[0] + 1) % cfg.vocab_size
            b["labels"] = labels
            return b
        return broken
    monkeypatch.setattr(pipeline, "make_batch_fn", make)
    assert not _execute(tiny_cell("tiny3.train"))["correct"]


def test_train_window_nonfinite(monkeypatch, tiny_cell):
    """Rounds whose losses turn non-finite in the window, after sound
    checked rounds, make the run not correct."""
    import numpy as np
    import harness as H
    kind = H.kind_module("train")
    real, calls = kind.Program.round, {"n": 0}
    cell = tiny_cell("tiny2.train")

    def round_(self, batch):
        calls["n"] += 1
        loss = real(self, batch)
        late = calls["n"] > cell.traffic["check_rounds"] + 1
        return jax.device_put(np.float32("nan")) if late else loss
    monkeypatch.setattr(kind.Program, "round", round_)
    monkeypatch.setattr(H, "kind_module", lambda name, bench_dir=None: kind)
    line = _execute(cell)
    assert not line["correct"]
    assert line["failed"] > 0
    assert line["checks"]["window_nonfinite"]["value"] == line["failed"]
    assert all(c["value"] <= c["limit"] for k, c in line["checks"].items()
               if k != "window_nonfinite"), line["checks"]


def _wrap_event(monkeypatch, wrap):
    from repro import optim
    monkeypatch.setattr(optim, "apply_event_ring_whatif",
                        wrap(optim.apply_event_ring_whatif))


def test_whatif_state_unchanged(monkeypatch, tiny_cell):
    _wrap_event(monkeypatch, lambda real: (
        lambda spec, ring, s, res, *rest: (ring, s, res)))
    assert not _execute(tiny_cell("tiny.whatif"))["correct"]


def test_whatif_half_batch(monkeypatch, tiny_cell):
    def wrap(real):
        def broken(spec, ring, s, res, a, wstar, ts, coef, lrs, prev, slot):
            h = ts.shape[0] // 2
            return real(spec, ring, s, res, a, wstar, ts[:h],
                        coef[:h] * (ts.shape[0] / h), lrs, prev, slot)
        return broken
    _wrap_event(monkeypatch, wrap)
    assert not _execute(tiny_cell("tiny.whatif"))["correct"]


def test_whatif_answer_altered(monkeypatch, tiny_cell):
    def wrap(real):
        def broken(spec, ring, s, res, a, wstar, ts, coef, lrs, prev, slot):
            ring, s, res = real(spec, ring, s, res, a, wstar, ts, coef, lrs,
                                prev, slot)
            return ring.at[slot, ::97].add(0.5), s, res
        return broken
    _wrap_event(monkeypatch, wrap)
    assert not _execute(tiny_cell("tiny.whatif"))["correct"]
