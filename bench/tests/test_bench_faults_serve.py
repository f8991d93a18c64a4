"""Runs of the serving cells with the timed path broken underneath come
out not correct, once for each fault a serving cell can have."""
import pytest

import _tiny

CELLS = ["log1d.serve", "dust.serve"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    ok, checks = _tiny.run_once(name)
    assert ok, checks


def _half_batch(monkeypatch):
    """Moments merged over half of each slab's rows, the mean taken over
    the rest."""
    import repro.launch.serve_gp as sg

    real = sg._welford_merge
    monkeypatch.setattr(sg, "_welford_merge", lambda c, m, m2, b: real(
        c, m, m2, b[:max(1, len(b) // 2)]))


def _answer_altered(monkeypatch):
    """The first row of every slab off by one part in a thousand."""
    from repro.core.icr import ICR

    real = ICR.apply_sqrt_batch
    monkeypatch.setattr(ICR, "apply_sqrt_batch",
                        lambda self, m, xi: real(self, m, xi).at[0]
                        .multiply(1.001))


def _sample_refused(monkeypatch):
    """Every sample request of the window refused where it is made: that
    kind is never answered."""
    import program

    real = program.request

    def request(r):
        req = real(r)
        if req.kind == "sample":
            req.error, req.done = "refused", True
        return req

    monkeypatch.setattr(program, "request", request)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_half_batch, _answer_altered,
                                   _sample_refused])
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    ok, checks = _tiny.run_once(name)
    assert not ok, checks
