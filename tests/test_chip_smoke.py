"""chip_smoke.py's phases on the CPU at the tiny preset: the same platform
and Service paths, log parsing and checks the chip run relies on. The
script itself refuses to run without a TPU; these tests call its phases."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402


@pytest.fixture
def clock():
    c = chip_smoke.CompileClock()
    yield c
    c.close()


def test_train_phase_completes_with_checkpoints(clock):
    out = chip_smoke.train_phase(
        clock, train=dict(chip_smoke.TRAIN_SPEC, tiny=True, seq=64, batch=2))
    assert out["status"] == "COMPLETED"
    assert out["steps"] == chip_smoke.TRAIN_SPEC["steps"]
    assert out["checkpoints"] == [10, 15]
    assert out["steady_step_s"] > 0


def test_serve_phase_answers_with_engine_tokens(clock):
    out = chip_smoke.serve_phase(clock, tiny=True,
                                 payload={"prompt_len": 16, "gen": 8})
    assert out["requests"] == chip_smoke.N_REQUESTS
    assert all(len(t) == 8 for t in out["first_tokens"])
    assert out["small_reference_margin_ratio"] <= chip_smoke.MAX_MARGIN_RATIO
