"""CPU tests of the readers of the program's tick records
(bench/program_ticks.py and the three readers over it) on a synthetic
run and synthetic records."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import run as harness  # noqa: E402
from repro.obs import spans  # noqa: E402
from repro.obs.spans import SpanRecord, SpanTotal, TickRecord  # noqa: E402

READERS = ("control_plane_ms.train", "learner_host_ms.train",
           "gc_pause_ms.train")


def _record(tick_s, learner_s, wait_s, steps, gc_s=0.0):
    totals = {"ffdl.tick": SpanTotal(tick_s, tick_s - learner_s, 1),
              "ffdl.learner.tick": SpanTotal(learner_s, 0.0, 1),
              "ffdl.learner.wait": SpanTotal(wait_s, wait_s, steps + 1)}
    counters = {"learner.steps": steps}
    if gc_s:
        counters["gc.pause_s"] = gc_s
    return TickRecord(shard="shard-0",
                      spans=(SpanRecord("ffdl.tick", 0, int(tick_s * 1e9),
                                        -1),),
                      totals=totals, counters=counters)


def _run(steps):
    return {"timers": {"ticks": [{"wall_s": 2.5, "steps": n} for n in steps],
                       "tokens_per_step": 8192, "seq": 2048}}


@pytest.fixture
def records(monkeypatch):
    """Three ticks before the window (set-up, one step each) and three in
    it; the recorder hands out the last ones."""
    kept = [_record(1.0, 0.9, 0.8, 1) for _ in range(3)] + [
        _record(2.550, 2.544, 2.530, 5),
        _record(2.560, 2.552, 2.537, 5, gc_s=0.004),
        _record(2.552, 2.545, 2.531, 5)]
    monkeypatch.setattr(spans, "recent_ticks",
                        lambda n=None: kept if n is None else kept[-n:])
    return kept


def test_readers_take_the_windows_ticks(records):
    run = _run([5, 5, 5])
    assert harness.read_metric("control_plane_ms.train", run) \
        == pytest.approx(7.0)  # median of 6, 8, 7 ms
    # (14 + 15 + 14 ms of learner host time) over 15 steps
    assert harness.read_metric("learner_host_ms.train", run) \
        == pytest.approx(43 / 15)
    assert harness.read_metric("gc_pause_ms.train", run) == pytest.approx(4)


@pytest.mark.parametrize("name", READERS)
def test_readers_raise_where_the_steps_do_not_match(records, name):
    with pytest.raises(RuntimeError, match="steps"):
        harness.read_metric(name, _run([5, 5, 4]))
    with pytest.raises(RuntimeError, match="steps"):
        harness.read_metric(name, _run([5] * 4))  # a set-up tick crept in


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_the_programs_records(monkeypatch,
                                                           name):
    assert harness.read_metric(name, _run([])) is None
    # a checkout of the program with no span recorder
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    assert harness.read_metric(name, _run([5, 5])) is None


def test_new_metrics_are_in_benchmark_json():
    spec = harness.load_spec()
    per_layer = {mt["name"]: mt for mt in spec["per_layer"]}
    for name in READERS:
        mt = per_layer[name]
        assert mt["moves"] == "train_tokens_per_s"
        assert mt["workloads"] == ["train.smollm-360m.steady"]
        assert (ROOT / "bench" / "metrics" / f"{name}.py").is_file()
