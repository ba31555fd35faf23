"""The harness on the CPU: seeded traffic, percentiles, a window that
closes on time, and a command that refuses to run without a TPU."""
from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from perfbench import stats
from perfbench import traffic as TR
from perfbench.tests import tiny

LONGDOC = json.loads((tiny.PERFBENCH / "mixes" / "longdoc.json").read_text())
SEED = 2 ** 33 + 12345          # seeds may need more than 32 bits


def _specs(mix, seed, n):
    t = TR.Traffic(mix, 102400, seed)
    return t, [t.next() for _ in range(n)]


def test_same_seed_same_traffic_other_seed_same_sizes():
    t1, a = _specs(LONGDOC, SEED, 300)
    t2, b = _specs(LONGDOC, SEED, 300)
    _, c = _specs(LONGDOC, SEED + 1, 300)
    for x, y in zip(a, b):
        assert x.doc == y.doc and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)
    assert all(np.array_equal(d1, d2)
               for d1, d2 in zip(t1.documents, t2.documents))
    # another seed: other order and tokens, the same multiset of sizes
    assert [s.max_new for s in a] != [s.max_new for s in c]
    assert sorted(s.max_new for s in a[:TR.POOL]) == \
        sorted(s.max_new for s in c[:TR.POOL])


def test_traffic_follows_the_mix_file():
    t, specs = _specs(LONGDOC, SEED, TR.POOL)
    docs = LONGDOC["documents"]
    assert len(t.documents) == docs["count"]
    assert all(len(d) == docs["tokens"] for d in t.documents)
    out = LONGDOC["output_tokens"]
    lens = [s.max_new for s in specs]
    assert min(lens) >= out["low"] and max(lens) <= out["high"]
    # stratified uniform: the pool's mean is the distribution's
    assert abs(np.mean(lens) - (out["low"] + out["high"]) / 2) < 1.0
    for s in specs:
        n = LONGDOC["unique_prompt_tokens"]["value"]
        assert len(s.prompt) == docs["tokens"] + n
        assert np.array_equal(s.prompt[:docs["tokens"]], t.documents[s.doc])
    counts = np.bincount([s.doc for s in specs], minlength=docs["count"])
    assert counts.min() == counts.max() == TR.POOL // docs["count"]


def test_open_loop_arrivals_are_poisson_at_the_rate():
    mix = {"loop": "open", "rate_per_s": 2.0,
           "unique_prompt_tokens": {"dist": "loguniform", "low": 128,
                                    "high": 4096},
           "output_tokens": {"dist": "loguniform", "low": 32, "high": 512}}
    _, specs = _specs(mix, SEED, TR.POOL)
    gaps = np.diff([0.0] + [s.due for s in specs])
    assert abs(gaps.mean() - 0.5) < 0.02
    lens = [len(s.prompt) for s in specs]
    assert 128 <= min(lens) and max(lens) <= 4096
    assert abs(np.median(lens) - math.sqrt(128 * 4096)) < 40


def test_percentile_nearest_rank_and_infinity():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile([3.0], 95) == 3.0
    # a request with no first token counts as +inf and pushes the tail out
    assert stats.percentile(xs[:-10] + [math.inf] * 10, 90) == 90
    assert stats.percentile(xs[:-11] + [math.inf] * 11, 90) == math.inf
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_token_cost_is_the_papers_model():
    b, f = stats.token_cost("fp8_e4m3", 512, 64, 1)
    assert (b, f) == (644, 2176)
    b, f = stats.token_cost("fp8_e4m3", 512, 64, 128)
    assert f / b == pytest.approx(432.5, abs=0.1)


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    from perfbench import harness
    seconds = 2.0
    res = harness.run_cell(
        bench=tiny.bench(), cell=tiny.bench()["workloads"][0],
        cfg=tiny.config(), mix=tiny.mix(), limits=tiny.limits(),
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        seed=SEED, seconds=seconds, trace=False, t_start=time.perf_counter(),
        root=tmp_path_factory.mktemp("root"))
    return res, seconds


def test_window_closes_on_time_with_requests_in_flight(tiny_run):
    res, seconds = tiny_run
    m = res["metrics"]
    assert set(m) == {"output_tok_s", "itl_p95_ms", "setup_s"}
    # attempted counts every request the window saw, finished or not;
    # the 4 closed-loop clients still hold requests when it closes
    assert res["in_flight"] == tiny.mix()["clients"]
    assert res["attempted"] >= res["in_flight"]
    assert res["failed"] == 0
    # one step past --seconds at most: the loop never waits for a drain
    assert seconds <= res["measured_s"] < seconds + 1.0
    assert m["output_tok_s"]["value"] > 0
    assert m["itl_p95_ms"]["value"] < seconds * 1e3
    assert res["compiles_in_window"] == 0
    assert res["device"]["platform"] == "cpu"
    assert res["correct"] is True
    assert list(res)[-1] == "check"


def test_open_loop_window_sends_requests_when_due(tmp_path):
    from perfbench import harness
    seconds, mix = 3.0, tiny.open_mix()
    res = harness.run_cell(
        bench=tiny.bench(), cell=tiny.bench()["workloads"][0],
        cfg=tiny.config(), mix=mix, limits=tiny.limits(),
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        seed=SEED, seconds=seconds, trace=False, t_start=time.perf_counter(),
        root=tmp_path)
    # every chunk shape and row count was warmed before the window
    assert res["compiles_in_window"] == 0
    # the requests due in the window: the seed's arrivals up to its close
    t = TR.Traffic(mix, 512, SEED)
    due = 0
    while t.next().due <= res["measured_s"]:
        due += 1
    assert res["attempted"] == due
    assert res["failed"] == 0
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert res["correct"] is True


class _Req:
    def __init__(self, due, tokens):
        self.due, self.tokens = due, tokens


def test_end_to_end_readers_count_only_the_window():
    from perfbench import harness
    ctx = {"t0": 10.0, "t_end": 20.0, "window_s": 10.0, "setup_s": 5.5,
           "requests": [_Req(9.0, [9.5, 10.5, 11.0, 11.5]),
                        _Req(12.0, [19.0, 20.0, 20.5]),
                        _Req(19.9, [])]}
    read = lambda name: harness.load_reader("end_to_end", name)(ctx)  # noqa
    assert read("output_tok_s") == pytest.approx(5 / 10.0)
    # in-window gaps: 500, 500 (first request), 1000 (second) ms
    assert read("itl_p95_ms") == pytest.approx(1000.0)
    assert read("setup_s") == 5.5
    ctx["requests"] = [_Req(9.0, [9.5, 10.5])]
    assert read("itl_p95_ms") == math.inf


def test_every_metric_has_its_reader():
    bench = tiny.bench()
    for kind, metrics in (("end_to_end", bench["end_to_end"]),
                          ("layer_metrics", bench["per_layer"])):
        for m in metrics:
            assert (tiny.PERFBENCH / kind / f"{m['name']}.py").is_file()


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "v2lite-longdoc",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_exits_nonzero_without_a_tpu():
    p = _run_cli(tiny.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.PERFBENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
