"""A training cell's run on the CPU at a tiny size, past the harness's
look for a chip: sound, it is correct; with the timed path broken
underneath (a step that leaves the state unchanged, half of each batch
left out), ``correct`` comes out false; and with the control, the
reference at float8, in the program's place, it comes out false too.

The limits are set by the cell's rules from readings at this size
(``data/tiny-limits.json``, PERF.md): at the cell's own size the chip
reads each number lower, and ``bench/calibrate.py`` judges the control
there with the cell's limits."""

import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import bench.generator as gen  # noqa: E402
import bench.train as T  # noqa: E402
from bench import run as harness  # noqa: E402
from bench.compile_clock import CompileClock  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SPEC = {"configs": [{"name": "tiny-smollm",
                     "file": "bench/tests/data/tiny-smollm.json"}],
        "workloads": [{"name": "tiny", "config": "tiny-smollm",
                       "traffic": "tiny-train", "chips": 1}],
        "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}


def _limits():
    return json.loads((DATA / "tiny-limits.json").read_text())["limits"]


def _run(monkeypatch, seed):
    monkeypatch.setattr(gen, "TRAFFIC_DIR", DATA)
    clock = CompileClock()
    try:
        return harness.execute(SPEC, "tiny", seed, 1.0, False, clock,
                               time.perf_counter(), "cpu",
                               limits=_limits())[0]
    finally:
        clock.close()


def _break(monkeypatch, wrap):
    build = T.build

    def broken(*args, **kwargs):
        objs = build(*args, **kwargs)
        objs["learner"]._train_step = wrap(objs["learner"]._train_step)
        return objs

    monkeypatch.setattr(T, "build", broken)


def test_sound_run_is_correct(monkeypatch):
    res = _run(monkeypatch, 2**33 + 11)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


def _unchanged(step):
    def same(state, batch):
        new, metrics = step(state, batch)
        return state._replace(step=new.step), metrics
    return same


def _half_batch(step):
    def half(state, batch):
        return step(state, {k: v[: v.shape[0] // 2]
                            for k, v in batch.items()})
    return half


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(monkeypatch, fault):
    _break(monkeypatch, fault)
    res = _run(monkeypatch, 2**33 + 12)
    assert not res["correct"], res["checks"]


def test_control_reads_far_above_the_program():
    cfg = json.loads((DATA / "tiny-smollm.json").read_text())
    traffic = json.loads((DATA / "tiny-train.json").read_text())
    seed = 2**32 + 5
    refr = T.reference_readings(cfg, traffic, seed)
    ctrl = T.compare(T.reference_readings(cfg, traffic, seed, lowp=True),
                     refr)
    objs = T.build(cfg, traffic, seed)
    prog = T.compare(T.warm(objs, cfg, traffic, seed), refr)
    objs.clear()
    assert ctrl["grad_gap"] >= 3 * prog["grad_gap"], (ctrl, prog)
    assert ctrl["loss_gap"] >= 3 * prog["loss_gap"], (ctrl, prog)


def test_control_in_the_programs_place_is_not_correct(monkeypatch):
    """The program still runs its steps and the window, but the readings
    compared are the control's: the harness's own comparison refuses."""
    warm = T.warm

    def control(objs, cfg, traffic, seed):
        warm(objs, cfg, traffic, seed)
        return T.reference_readings(cfg, traffic, seed, lowp=True)

    monkeypatch.setattr(T, "warm", control)
    res = _run(monkeypatch, 2**33 + 13)
    assert not res["correct"], res["checks"]
