"""CPU tests of the benchmark's yardstick: the trace reduction, the
training rows, the FLOP counters, the seeded weights, the
per-layer readers, and that every name in BENCHMARK.json finds its files.
"""

import json
import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import bench.flops as flops  # noqa: E402
import bench.generator as gen  # noqa: E402
import bench.peaks as peaks  # noqa: E402
import bench.trace as tr  # noqa: E402
from bench import run as harness  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


# -- trace reduction --------------------------------------------------------

def test_busy_union_and_gaps():
    ops = [(0, 10), (5, 20), (30, 40), (38, 45), (60, 70)]
    assert tr.merge(ops) == [(0, 20), (30, 45), (60, 70)]
    assert tr.busy_ns(ops, 10, 65) == 10 + 15 + 5
    assert tr.gaps(ops, 10, 65) == [(20, 30), (45, 60)]
    assert tr.gaps([], 0, 5) == [(0, 5)]


def test_op_name_drops_the_hlo_text():
    assert tr.op_name("%fusion.12 = bf16[4,5]{1,0} fusion(%p), kind=kLoop") \
        == "%fusion.12"
    assert tr.op_name("copy-start.3") == "copy-start.3"


def test_gap_takes_the_innermost_span():
    spans = [("bench.window", 0, 100), ("bench.tick", 10, 50),
             ("bench.tick_save", 20, 40)]
    assert tr.name_gap((25, 35), spans) == "bench.tick_save"
    assert tr.name_gap((60, 70), spans) == "bench.window"
    assert tr.name_gap((200, 210), spans) == "no bench span"


def test_reduce_recorded_trace():
    """A small trace in the form ``trace.load`` returns: overlapping
    operations, nested host spans, gaps inside and outside the spans."""
    rec = json.loads((DATA / "trace_sample.json").read_text())
    devices = {k: [tuple(e) for e in v] for k, v in rec["devices"].items()}
    spans = [tuple(s) for s in rec["spans"]]
    out = tr.reduce(devices, spans)
    lo, hi = [(s, e) for n, s, e in spans if n == "bench.traced"][0]
    ops = [(s, e) for _, s, e in devices[sorted(devices)[0]]]
    busy = tr.busy_ns(ops, lo, hi)
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert out["busy_s"] == pytest.approx(busy / 1e9)
    assert 0.0 <= out["idle_share"] <= 1.0
    idle = sum(e - s for s, e in tr.gaps(ops, lo, hi))
    assert busy + idle == hi - lo
    assert out["idle_share"] == pytest.approx(rec["expect"]["idle_share"])
    assert [g[0] for g in out["idle_gaps"]][:1] == \
        rec["expect"]["longest_gap_span"]
    assert len(out["device_ops"]) <= tr.TOP
    assert out["device_ops"][0][0] == rec["expect"]["top_op"]


# -- traffic ----------------------------------------------------------------

def test_train_rows_are_seeded_and_all_differ():
    traffic = gen.load("steady")
    one = gen.train_rows(traffic, 2**40 + 1, 49152, 3)
    assert (one["tokens"] == gen.train_rows(traffic, 2**40 + 1, 49152, 3)
            ["tokens"]).all()
    assert one["tokens"].shape == (4, 2048)
    assert (one["tokens"][:, 1:] == one["labels"][:, :-1]).all()
    rows = [tuple(r) for s in range(3) for r in
            gen.train_rows(traffic, 2**40 + 1, 49152, s)["tokens"]]
    assert len(set(rows)) == len(rows)


def test_sub_seed_takes_seeds_beyond_32_bits():
    seeds = {gen.sub_seed(s, "weights") for s in (0, 2**31 + 5, 2**40)}
    assert len(seeds) == 3 and all(0 <= s < 2**31 for s in seeds)


# -- counters against hand counts --------------------------------------------

def test_smollm_counts():
    m = _cfg("smollm-360m")["model"]
    # 32 x (960*64*(15+10) + 15*64*960 + 3*960*2560 + 2*960)
    # + 49152*960 + 960, as the published model has it
    assert flops.param_counts(m)["total"] == 361_821_120
    layer = 960 * 64 * 25 + 15 * 64 * 960 + 3 * 960 * 2560
    per_token = 32 * layer + 49152 * 960
    attn = 4 * 32 * 15 * 64
    assert flops.train_flops_per_token(m, 2048) == pytest.approx(
        3 * (2 * per_token + attn * 2049 / 2))
    assert flops.train_flops_per_token(m, 2048) == pytest.approx(2.549e9,
                                                                 rel=1e-3)


# Qwen/Qwen2.5-3B config.json: GQA with QKV bias, head_dim 128, tied
QWEN_3B = {"n_layers": 36, "d_model": 2048, "n_heads": 16, "n_kv_heads": 2,
           "d_ff": 11008, "vocab_size": 151936, "tie_embeddings": True,
           "qkv_bias": True}


def test_qwen_counts():
    m = QWEN_3B
    layer = 2048 * 128 * (16 + 4) + 16 * 128 * 2048 + 3 * 2048 * 11008
    bias = 128 * 20
    total = 36 * (layer + bias + 2 * 2048) + 151936 * 2048 + 2048
    assert flops.param_counts(m)["total"] == total
    assert 3.08e9 < total < 3.10e9
    per_token = 36 * layer + 151936 * 2048  # the biases add no product
    attn = 4 * 36 * 16 * 128
    assert flops.train_flops_per_token(m, 4096) == pytest.approx(
        3 * (2 * per_token + attn * 4097 / 2))


def test_peaks_are_keyed_and_strict():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert peaks.peak("TPU v5 lite")["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")


# -- seeded weights -----------------------------------------------------------

@pytest.mark.parametrize("path", ["bench/configs/smollm-360m.json",
                                  "bench/tests/data/tiny-smollm.json",
                                  "bench/tests/data/tiny-qwen.json"],
                         ids=["smollm-360m", "smollm-360m-tiny",
                              "qwen2.5-3b-tiny"])
def test_weights_have_the_programs_layout(path):
    import jax

    import bench.weights as W
    from repro.configs import get_config, get_tiny_config
    from repro.models import steps

    cfg = json.loads((ROOT / path).read_text())
    made = jax.eval_shape(lambda: W._make(W.frozen(cfg["model"]),
                                          W.base_key(1), None))
    get = get_tiny_config if cfg.get("tiny") else get_config
    want = steps.abstract_params(get(cfg["arch"]))
    assert jax.tree.structure(made) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_reference_weights_are_the_programs_in_float32():
    import jax
    import jax.numpy as jnp

    import bench.weights as W

    m = json.loads((DATA / "tiny-qwen.json").read_text())["model"]
    served = W.make(m, 2**35 + 3)
    master = W.make(m, 2**35 + 3, "float32")
    for a, b in zip(jax.tree.leaves(served), jax.tree.leaves(master)):
        assert b.dtype == jnp.float32
        assert (a.astype(jnp.float32) == b).all()
    other = W.make(m, 2**35 + 4)
    assert not (jax.tree.leaves(other)[0] == jax.tree.leaves(served)[0]).all()


def test_gaps_take_the_larger_of_leaf_and_median_norm():
    from bench.norms import gaps, median_gap, worst_gap

    ref = {"a": 1.0, "b": 2.0, "c": 4.0, "tiny": 0.01}
    got = {"a": 1.1, "b": 2.0, "c": 3.0, "tiny": 0.03}
    # the median of the kept leaves' norms is 2.0 (the upper middle)
    assert gaps(got, ref) == pytest.approx(
        {"a": 0.05, "b": 0.0, "c": 0.25, "tiny": 0.01})
    assert worst_gap(got, ref) == (pytest.approx(0.25), "c")
    assert median_gap(got, ref) == pytest.approx(0.03)
    assert worst_gap(got, ref, keep=["a", "b"]) == (pytest.approx(0.05),
                                                    "a")
    with pytest.raises(KeyError):
        gaps({"a": 1.0}, ref)


# -- per-layer readers --------------------------------------------------------

def _train_run(ticks):
    return {"timers": {"ticks": ticks, "tokens_per_step": 8192,
                       "seq": 2048},
            "model": _cfg("smollm-360m")["model"], "window_s": 10.0,
            "chips": 1, "peak": peaks.peak("TPU v5 lite"),
            "trace": {"idle_share": 0.25}}


def test_train_readers():
    ticks = [{"wall_s": 2.5, "steps": 5}, {"wall_s": 2.7, "steps": 5},
             {"wall_s": 2.6, "steps": 5}]
    run = _train_run(ticks)
    mfu = harness.read_metric("mfu.train", run)
    assert mfu == pytest.approx(100 * 15 * 8192 * flops.train_flops_per_token(
        run["model"], 2048) / 10.0 / 197e12)
    assert harness.read_metric("idle_share.train", run) == 25.0
    assert harness.read_metric("mfu.train", _train_run([])) is None
    run.pop("trace")
    assert harness.read_metric("idle_share.train", run) is None


# -- BENCHMARK.json finds its files -------------------------------------------

def test_every_name_in_benchmark_json_has_its_files():
    spec = harness.load_spec()
    bench = ROOT / "bench"
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in spec["workloads"]:
        assert (bench / "traffic" / f"{w['traffic']}.json").is_file()
        limits = json.loads((bench / "limits" / f"{w['name']}.json")
                            .read_text())["limits"]
        assert limits and all(v > 0 for v in limits.values())
        names = [mt["name"] for mt in harness.metrics_of(spec, w["name"],
                                                         "end_to_end")]
        assert "setup_s" in names and len(names) >= 2
        assert harness.metrics_of(spec, w["name"], "per_layer")
    for mt in spec["per_layer"]:
        assert (bench / "metrics" / f"{mt['name']}.py").is_file()
        assert mt["moves"] in [e["name"] for e in spec["end_to_end"]]


def test_trace_reduction_refuses_a_trace_without_device_work():
    with pytest.raises(ValueError):
        tr.reduce({}, [("bench.traced", 0, 10)])
    assert math.isclose(tr.reduce({"d": [("op", 0, 5)]},
                                  [("bench.traced", 0, 10)])["idle_share"],
                        0.5)
