"""CPU tests of the benchmark harness (``chipbench/``).

Run from the checkout root: ``python -m pytest chipbench/tests``. They
need no chip: the drivers run at the small sizes in
``chipbench/testdata`` with the harness's look for a chip skipped.
"""
from __future__ import annotations

import json
import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import gen, harness, trace_reduce  # noqa: E402
from chipbench import run as bench_run  # noqa: E402
from chipbench.counts import decoder as dec_counts  # noqa: E402
from chipbench.counts import gn_resnet as cnn_counts  # noqa: E402

TESTDATA = ROOT / "chipbench" / "testdata"
FAKE_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    return harness.load_json(ROOT / "BENCHMARK.json")


def _tiny_run(name, seed=2 ** 33 + 5, trace=False, fault=None,
              control=False, bench=None, traffic_dir=None,
              metrics_dir=None):
    return bench_run.run_cell(
        bench or harness.load_json(TESTDATA / "bench.json"), name, seed,
        2.0, trace, {"fault": fault, "control": control,
                     "peaks": FAKE_PEAKS},
        check_chips=False, traffic_dir=traffic_dir or TESTDATA / "traffic",
        metrics_dir=metrics_dir)


# ---------------------------------------------------------------- trace

def _covered(intervals, window):
    """Covered seconds by counting open intervals at each edge: an
    algorithm independent of the merge in ``trace_reduce.union``."""
    lo, hi = window
    edges = sorted([(max(s, lo), 1) for s, e in intervals if e > lo]
                   + [(min(e, hi), -1) for s, e in intervals if e > lo])
    total, depth, last = 0.0, 0, lo
    for t, d in edges:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


def test_recorded_trace_busy_idle_and_program_time():
    trace = trace_reduce.Trace.from_json(
        json.loads((TESTDATA / "trace_cnn_v5e.json").read_text()))
    dev = trace.devices[0]
    w = trace.window
    want = _covered([(s, e) for _, s, e in dev.ops], w)
    assert trace_reduce.busy_s(trace) == pytest.approx(want, rel=1e-9)
    span = w[1] - w[0]
    assert trace_reduce.idle_share(trace, w) == pytest.approx(
        1 - want / span, rel=1e-9)
    secs, runs = trace_reduce.program_time(trace, "jit_step")
    mods = [(s, e) for n, s, e in dev.modules if n == "jit_step"]
    assert runs == len(mods) > 0
    assert secs == pytest.approx(sum(e - s for s, e in mods))
    assert trace_reduce.exposed_collective_s(trace) == 0.0
    top = trace_reduce.top_ops(trace, 3)
    assert len(top) == 3 and top[0][1] >= top[1][1] >= top[2][1]


def test_exposed_collectives_and_idle_gaps():
    ops = [("%fusion.1 = f32[8] fusion(x)", 0.0, 1.0),
           ("%all-gather.2 = f32[8] all-gather(y)", 0.5, 2.0),
           ("%all-reduce.3 = f32[8] all-reduce(z)", 3.0, 4.0),
           ("%fusion.4 = f32[8] fusion(w)", 3.5, 3.6)]
    trace = trace_reduce.Trace(
        [trace_reduce.Device(ops, [("jit_step", 0.0, 4.0)])],
        [("decode", 2.0, 2.9), ("admit", 2.95, 3.0)], (0.0, 5.0))
    # all-gather exposed over [1, 2], all-reduce over [3, 3.5] + [3.6, 4]
    assert trace_reduce.exposed_collective_s(trace) == pytest.approx(1.9)
    assert trace_reduce.busy_s(trace) == pytest.approx(3.0)
    assert trace_reduce.idle_share(trace, (0.0, 5.0)) == pytest.approx(0.4)
    gaps = trace_reduce.idle_gaps(trace, (0.0, 5.0))
    assert gaps[0] == ["decode", pytest.approx(1.0)]
    assert gaps[1] == ["host", pytest.approx(1.0)]
    assert trace_reduce.op_label(ops[1][0]) == "all-gather.2"


# --------------------------------------------------------------- counts

def test_gn_resnet_counts_by_hand():
    cfg = harness.load_json(ROOT / "chipbench/configs/paper-gn-resnet18.json")
    stem = 2 * 32 * 32 * 9 * 3 * 64
    stage1 = 4 * 2 * 32 * 32 * 9 * 64 * 64
    later = 0
    for size, cin, cout in ((16, 64, 128), (8, 128, 256), (4, 256, 512)):
        hw = size * size
        later += 2 * hw * 9 * cin * cout + 3 * 2 * hw * 9 * cout * cout \
            + 2 * hw * cin * cout
    head = 2 * 512 * 10
    fwd = stem + stage1 + later + head
    assert cnn_counts.forward_flops_per_sample(cfg) == fwd
    assert cnn_counts.train_flops_per_sample(cfg) == 3 * fwd - stem
    assert 3.2e9 < cnn_counts.train_flops_per_sample(cfg) < 3.4e9


def test_granite_counts_by_hand():
    cfg = harness.load_json(ROOT / "chipbench/configs/granite-3.0-2b.json")
    s = dec_counts.summary(cfg)
    attn = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048
    mlp = 3 * 2048 * 8192
    assert s["linear_flops_per_token"] == 2 * (40 * (attn + mlp)
                                               + 2048 * 49155)
    assert s["kv_bytes_per_token"] == 81920
    assert s["attn_flops_per_pair"] == 4 * 40 * 32 * 64
    weights = (40 * (attn + mlp + 2 * 2048) + 2048 + 2048 * 49155) * 2
    assert s["weight_bytes_per_step"] == weights
    assert dec_counts.decode_step_bytes(s, 2, 100) == weights + 81920 * 102


# -------------------------------------------------------------- traffic

def test_same_seed_same_traffic():
    t = harness.load_json(ROOT / "chipbench/traffic/chat-poisson.json")
    a = gen.request_trace(t, 2 ** 40 + 3, 20.0, 49155)
    b = gen.request_trace(t, 2 ** 40 + 3, 20.0, 49155)
    c = gen.request_trace(t, 7, 20.0, 49155)
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    # another seed: the same schedule, other token ids
    for key in ("arrival_s", "max_new_tokens"):
        assert [r[key] for r in a] == [r[key] for r in b] \
            == [r[key] for r in c]
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in c]
    assert not all((x["prompt"] == y["prompt"]).all()
                   for x, y in zip(a, c))
    assert len(a) == round(t["rate_per_s"] * 20.0)
    assert all(0 <= r["arrival_s"] < 20.0 for r in a)
    assert set(len(r["prompt"]) for r in a) <= set(t["prompt"]["menu"])


def test_same_seed_same_images_and_partition():
    import jax
    make = jax.jit(gen.images_fn(64, 10, 16))
    x1, y1 = make(harness.seed_key(2 ** 35 + 1))
    x2, y2 = make(harness.seed_key(2 ** 35 + 1))
    x3, _ = make(harness.seed_key(1))
    assert (np.asarray(x1) == np.asarray(x2)).all()
    assert not (np.asarray(x1) == np.asarray(x3)).all()
    labels = np.asarray(y1).astype(np.int64)
    p1 = gen.dirichlet_partition(labels, 8, 10, 2, 0.3, 5)
    p2 = gen.dirichlet_partition(labels, 8, 10, 2, 0.3, 5)
    assert all((a == b).all() for a, b in zip(p1, p2))
    assert sorted(np.concatenate(p1).tolist()) == list(range(64))


# ---------------------------------------------------- BENCHMARK.json

def test_benchmark_names_units_and_files():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in b["configs"]] \
        + [w["name"] for w in b["workloads"]] \
        + [m["name"] for m in b["end_to_end"] + b["per_layer"]] \
        + [w["traffic"] for w in b["workloads"]] \
        + [k for c in b["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    metric_names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    for m in b["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert (ROOT / "chipbench/metrics" / f"{m['name']}.py").exists()
    for c in b["configs"]:
        assert (ROOT / c["file"]).exists()
        assert harness.load_json(ROOT / c["file"])["reduced"] == c["reduced"]
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        t = harness.load_json(ROOT / "chipbench/traffic"
                              / f"{w['traffic']}.json")
        assert (ROOT / "chipbench/drivers" / f"{t['driver']}.py").exists()
        assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    assert len(json.dumps(b)) < 64 * 1024


# ------------------------------------------- found by name, as files

def test_config_traffic_and_metric_added_as_files(tmp_path):
    bench = harness.load_json(TESTDATA / "bench.json")
    traffic_dir = tmp_path / "traffic"
    traffic_dir.mkdir()
    t = harness.load_json(TESTDATA / "traffic/tiny-gpsl.json")
    t["protocol"]["global_batch"] = 8
    (traffic_dir / "tiny-gpsl-b8.json").write_text(json.dumps(t))
    cfg = harness.load_json(TESTDATA / "tiny-cnn.json")
    cfg.update(name="tiny-cnn-3", stage_widths=[8, 16, 24])
    cfg_path = tmp_path / "tiny-cnn-3.json"
    cfg_path.write_text(json.dumps(cfg))
    metrics_dir = tmp_path / "metrics"
    metrics_dir.mkdir()
    (metrics_dir / "window_steps.train.py").write_text(
        "UNIT = 'count'\n\ndef read(record):\n"
        "    return record['steps'] if record.get('kind') == 'train' "
        "else None\n")
    bench["configs"].append({"name": "tiny-cnn-3", "source": "test",
                             "file": str(cfg_path), "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "tiny-cnn-3.b8",
                               "config": "tiny-cnn-3",
                               "traffic": "tiny-gpsl-b8", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "window_steps.train",
                               "unit": "count", "better": "higher",
                               "source": "host_clock", "layer": "loop",
                               "moves": "train_samples_per_s",
                               "workloads": ["tiny-cnn-3.b8"]})
    result, checks = _tiny_run("tiny-cnn-3.b8", trace=True, bench=bench,
                               traffic_dir=traffic_dir,
                               metrics_dir=metrics_dir)
    assert result["correct"], checks
    assert result["metrics"]["window_steps.train"]["value"] \
        == result["attempted"] > 0


# ------------------------------------------------------------ no chip

def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "cnn-gpsl-dirichlet100", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


# ------------------------------------------- sound runs and broken ones

def test_training_cell_correct_when_sound_and_not_when_broken():
    result, _ = _tiny_run("cnn-gpsl-dirichlet100")
    assert result["correct"]
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert result["details"]["compiles_in_window"] == 0
    for fault in ("unchanged", "half_batch"):
        result, checks = _tiny_run("cnn-gpsl-dirichlet100", fault=fault)
        assert not result["correct"], (fault, checks)


def test_serving_cell_correct_when_sound_and_not_when_broken():
    result, _ = _tiny_run("granite3-2b-chat-poisson")
    assert result["correct"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["details"]["compiles_in_window"] == 0
    result, checks = _tiny_run("granite3-2b-chat-poisson", fault="token")
    assert not result["correct"], checks


def test_training_control_comes_out_not_correct():
    """The control, the reference's three steps in bfloat16 (the
    precision below the configuration's float32), put in the program's
    place, fails the cell's comparison: the parameters' change after
    three steps lies beyond its limit."""
    result, checks = _tiny_run("cnn-gpsl-dirichlet100", control=True)
    assert not result["correct"], checks
    by_name = {c["name"]: c for c in checks}
    assert not by_name["delta_gap"]["ok"], checks
    assert result["details"]["program_delta_gap"] \
        <= by_name["delta_gap"]["limit"]


@pytest.mark.parametrize("seed", [2 ** 33 + 11, 2 ** 33 + 12, 2 ** 33 + 13])
def test_serving_control_comes_out_not_correct(seed):
    """The control, the token that an int8 computation (the precision
    below the served bfloat16) puts first at each position of the served
    requests, put in the program's place, fails the cell's limit on the
    logit gap; the program's own served tokens pass it. The cell is a
    bfloat16 decoder that a CPU test can hold, serving 64 tokens after
    prompts of 64 on eight rows, with the chat cell's limit."""
    result, checks = _tiny_run("small-decoder.chat", seed=seed,
                               control=True)
    assert not result["correct"], checks
    limit = harness.load_json(
        ROOT / "chipbench/traffic/chat-poisson.json")["limits"][
            "served_logit_gap"]
    gap = {c["name"]: c for c in checks}["served_logit_gap"]
    assert gap["limit"] == limit and gap["value"] > limit, checks
    assert result["details"]["program_gap"] <= limit
