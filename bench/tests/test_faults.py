"""A whole run past the chip look, at a size the CPU holds, with the timed
path broken underneath: ``correct`` has to come out false.  The sound run
beside each shows the same check passing."""

import dataclasses

import pytest

from conftest import tiny_serving, tiny_stencil

STENCIL = "stencil7-l512-loop"
SERVE = "granite-chat-steady"


def _patch_backend(monkeypatch, wrap):
    import repro.kernels  # noqa: F401
    from repro.core.portable import get_kernel
    backends = get_kernel("stencil7").backends
    be = backends["pallas_interpret"]
    monkeypatch.setitem(backends, "pallas_interpret",
                        dataclasses.replace(be, fn=wrap(be.fn)))


def test_stencil_sound(run_cell, capsys):
    out = run_cell(STENCIL, tiny_stencil, capsys=capsys)
    assert out["correct"] is True and out["attempted"] > 0


@pytest.mark.parametrize("fault", ["answer_altered", "state_unchanged"])
def test_stencil_faults(run_cell, capsys, monkeypatch, fault):
    def wrap(fn):
        if fault == "answer_altered":       # one cell off by one
            return lambda u, **kw: fn(u, **kw).at[3, 30, 60].add(1.0)
        return lambda u, **kw: u + 0.0      # returns its input unchanged
    _patch_backend(monkeypatch, wrap)
    out = run_cell(STENCIL, tiny_stencil, capsys=capsys)
    assert out["correct"] is False
    assert out["checks"]["stencil_rel_err"]["value"] > \
        out["checks"]["stencil_rel_err"]["limit"]


def test_serving_sound(run_cell, capsys):
    out = run_cell(SERVE, tiny_serving, seconds=3.0, capsys=capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["tok_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_serving_faults(run_cell, capsys, monkeypatch, fault):
    from repro.serving import engine as E
    if fault == "token_altered":            # decoded tokens changed
        sample = E.sample_per_slot
        monkeypatch.setattr(E, "sample_per_slot",
                            lambda *a: (sample(*a) + 1) % 2048)
    else:                                   # decode returns its cache as is
        step = E.decode_step
        monkeypatch.setattr(E, "decode_step", lambda p, c, t, pos, caches:
                            (step(p, c, t, pos, caches)[0], caches))
    out = run_cell(SERVE, tiny_serving, seconds=3.0, capsys=capsys)
    print(fault, out["checks"])
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]
