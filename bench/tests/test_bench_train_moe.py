"""An MoE training cell's run (``bench/train_moe.py``) on the CPU at a
tiny size, through the harness: sound, it is correct; with the timed
path broken underneath (a step that leaves the state unchanged, half of
each batch left out) ``correct`` comes out false, and with the control,
the reference at float8, in the program's place too.

The limits are set by the cell's rules from readings at this size over
12 seeds (``data/tiny-moe-limits.json``, PERF.md)."""

import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import bench.generator as gen  # noqa: E402
import bench.train_moe as TM  # noqa: E402
from bench import run as harness  # noqa: E402
from bench.compile_clock import CompileClock  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SPEC = {"configs": [{"name": "tiny-moonlight",
                     "file": "bench/tests/data/tiny-moonlight.json"}],
        "workloads": [{"name": "tiny", "config": "tiny-moonlight",
                       "traffic": "tiny-moe", "chips": 1}],
        "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}


def _run(monkeypatch, seed):
    monkeypatch.setattr(gen, "TRAFFIC_DIR", DATA)
    limits = json.loads((DATA / "tiny-moe-limits.json").read_text())["limits"]
    clock = CompileClock()
    try:
        return harness.execute(SPEC, "tiny", seed, 1.0, False, clock,
                               time.perf_counter(), "cpu", limits=limits)[0]
    finally:
        clock.close()


def _break(monkeypatch, wrap):
    build = TM.build

    def broken(*args, **kwargs):
        objs = build(*args, **kwargs)
        objs["learner"]._train_step = wrap(objs["learner"]._train_step)
        return objs

    monkeypatch.setattr(TM, "build", broken)


def test_sound_run_is_correct(monkeypatch):
    res = _run(monkeypatch, 2**33 + 11)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


def _unchanged_state(step):
    """The step runs, but the state it hands back is the one it was given
    (a copy: the step takes the buffers of the state it is given)."""
    import jax
    import jax.numpy as jnp

    def same(state, batch):
        keep = jax.tree.map(jnp.copy, state)
        new, metrics = step(state, batch)
        return keep._replace(step=new.step), metrics
    return same


def _half_batch(step):
    def half(state, batch):
        return step(state, {k: v[: v.shape[0] // 2]
                            for k, v in batch.items()})
    return half


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(monkeypatch, fault):
    _break(monkeypatch, fault)
    res = _run(monkeypatch, 2**33 + 12)
    assert not res["correct"], res["checks"]


def test_control_in_the_programs_place_is_not_correct(monkeypatch):
    warm = TM.warm

    def control(objs, cfg, traffic, seed):
        warm(objs, cfg, traffic, seed)
        return TM.reference_readings(cfg, traffic, seed, objs["bias"],
                                     lowp=True)

    monkeypatch.setattr(TM, "warm", control)
    res = _run(monkeypatch, 2**33 + 13)
    assert not res["correct"], res["checks"]


def test_traced_run_writes_the_step_text_with_the_moe_scopes(monkeypatch,
                                                             tmp_path):
    """The text a traced run writes beside its trace names the program's
    scope of each operation: the layer's routing, dispatch and combine."""
    from bench import device_ops

    monkeypatch.setattr(gen, "TRAFFIC_DIR", DATA)
    cfg = json.loads((DATA / "tiny-moonlight.json").read_text())
    traffic = gen.load("tiny-moe")
    objs = TM.build(cfg, traffic, 2**33 + 14)
    TM.warm(objs, cfg, traffic, 2**33 + 14)
    TM.write_step_hlo(objs["learner"], traffic, tmp_path)
    objs.clear()
    paths = device_ops.op_scopes(tmp_path).values()
    for scope in ("moe.route", "moe.dispatch", "moe.experts", "moe.combine",
                  "mla"):
        assert any(scope in p for p in paths), scope


def test_dispatch_reader_sums_the_scoped_ops_a_step(monkeypatch, tmp_path):
    from bench import device_ops

    (tmp_path / device_ops.STEP_HLO).write_text("\n".join([
        '  %sort.1 = s32[8]{0} sort(%a), metadata={op_name="j/moe.dispatch/'
        'sort"}',
        '  %fusion.2 = f32[8]{0} fusion(%b), kind=kLoop, calls=%f, '
        'metadata={op_name="j/transpose(jvp())/moe.combine/mul"}',
        '  %gmm.3 = bf16[8]{0} custom-call(%c), metadata={op_name="j/'
        'moe.experts/gmm"}',
        '  ROOT %fusion.4 = f32[8]{0} fusion(%d), kind=kLoop, calls=%g, '
        'metadata={op_name="j/moe.route/logistic"}',
        '  %p.5 = f32[8]{0} parameter(0)']))
    monkeypatch.setattr(device_ops, "window_ops", lambda d: [
        ("%sort.1", 0.5), ("%fusion.2", 0.25), ("%gmm.3", 4.0),
        ("%fusion.4", 0.25), ("%p.5", 1.0), ("%sort.1", 0.5)])
    run = {"trace_dir": tmp_path,
           "timers": {"ticks": [{"steps": 2}, {"steps": 3}]}}
    got = harness.read_metric("moe_dispatch_ms.moe8k", run)
    assert got == pytest.approx(1e3 * 1.5 / 5)
    assert harness.read_metric("moe_dispatch_ms.moe8k",
                        dict(run, trace_dir=tmp_path / "none")) is None


def test_moe_metrics_are_in_benchmark_json():
    spec = harness.load_spec()
    cell = "train.moonlight-16b-a3b.moe8k"
    per_layer = {mt["name"]: mt for mt in spec["per_layer"]}
    for name in ("mfu.moe8k", "gmm_roofline.moe8k", "moe_dispatch_ms.moe8k"):
        assert per_layer[name]["workloads"] == [cell]
        assert per_layer[name]["moves"] == "train_tokens_per_s"
        assert (ROOT / "bench" / "metrics" / f"{name}.py").is_file()
