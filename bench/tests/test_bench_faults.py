"""``correct`` comes out false when the timed path is broken underneath,
and when the control (a lower precision) stands in for the program.

Each fault is planted in the program's own entry that the window drives,
``SequencePlan.apply``; the rest of the run is the harness's, on the
CPU, at a tiny size.  The cells run on one chip, so there is no exchange
between chips to leave out.
"""
import pytest

from bench.tests import tiny


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny.make_checkout(str(tmp_path_factory.mktemp("checkout")))


def _unchanged(orig):
    def apply(self, A, *args, **kw):
        return A
    return apply


def _half_left_out(orig):
    def apply(self, A, *args, **kw):
        out = orig(self, A, *args, **kw)
        # every second row of the target left as it came in
        return out.at[1::2].set(A[1::2])
    return apply


def _altered(orig):
    def apply(self, A, *args, **kw):
        out = orig(self, A, *args, **kw)
        # one entry of every output off by about one standard deviation
        return out.at[..., 1, 2].add(1.0)
    return apply


FAULTS = {"unchanged": _unchanged, "half_left_out": _half_left_out,
          "altered": _altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell,entry", [(tiny.APPLY_CELL, "apply")])
def test_a_broken_timed_path_is_not_correct(checkout, monkeypatch, cell,
                                            entry, fault):
    from repro.core.sequence import SequencePlan

    monkeypatch.setattr(SequencePlan, entry,
                        FAULTS[fault](getattr(SequencePlan, entry)))
    result = tiny.run_cell(checkout, cell)
    assert result["correct"] is False, result["checks"]
    assert result["failed"] > 0


def test_the_apply_control_is_not_correct(checkout):
    """The program's own bfloat16 path in the float32 cell's place."""
    result = tiny.run_cell(checkout, tiny.APPLY_CELL, dtype="bfloat16")
    assert result["correct"] is False
    gap = result["checks"]["gap"]
    assert gap["value"] > 3 * gap["limit"]

