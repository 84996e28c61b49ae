"""The harness: files found by name, the refusals, one whole run at a
tiny size on the CPU, and the arithmetic of the end-to-end metrics."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from bench import costs, run, spec, weights
from bench.conftest import REPO, TINY_CONFIG, TINY_TRAFFIC, make_root
from bench.program import Admission, Tick

ARGS = ["--workload", "tiny.open", "--seed", str(2**33 + 5),
        "--seconds", "2"]


def _last_json(text: str):
    lines = [x for x in text.strip().splitlines() if x.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def test_each_cell_finds_its_files():
    for w in spec.load_benchmark(REPO)["workloads"]:
        cell = spec.load_cell(REPO, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.metric_reader(REPO, m["name"]))


def test_a_dropped_in_file_is_found_by_name(tmp_path):
    """A new configuration, traffic mix and metric are new files; no
    existing file of the benchmark changes."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"))
    before = {f: open(os.path.join(dp, f), "rb").read()
              for dp, _, fs in os.walk(os.path.join(root, "bench"))
              for f in fs}
    with open(os.path.join(root, "bench", "configs", "new.json"), "w") as f:
        json.dump(dict(TINY_CONFIG, name="new"), f)
    with open(os.path.join(root, "bench", "traffic", "burst.json"),
              "w") as f:
        json.dump(TINY_TRAFFIC, f)
    with open(os.path.join(root, "bench", "metrics", "answer.py"),
              "w") as f:
        f.write("def read(run):\n    return 42.0\n")
    bench = spec.load_benchmark(REPO)
    bench["configs"].append({"name": "new", "source": "x",
                             "file": "bench/configs/new.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new.burst", "config": "new",
                               "traffic": "burst", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "answer", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine", "moves": "itl_p50_ms",
                               "workloads": ["new.burst"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = spec.load_cell(root, "new.burst")
    assert cell.config["name"] == "new"
    assert cell.traffic == TINY_TRAFFIC
    assert [m["name"] for m in cell.per_layer][-1] == "answer"
    assert spec.metric_reader(root, "answer")(None) == 42.0
    assert "answer" not in {m["name"] for m in
                            spec.load_cell(root, "qwen14b.decode").per_layer}
    after = {f: open(os.path.join(dp, f), "rb").read()
             for dp, _, fs in os.walk(os.path.join(root, "bench"))
             for f in fs if f not in ("new.json", "burst.json",
                                      "answer.py")}
    assert {k: v for k, v in after.items() if k in before} == \
        {k: v for k, v in before.items() if k in after}


def _cmd(root, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", "qwen14b.decode", "--seed", "1", "--seconds", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _cmd(REPO)
    assert p.returncode != 0
    assert _last_json(p.stdout) is None
    assert "no TPU" in p.stderr


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    p = _cmd(root)
    assert p.returncode != 0
    assert _last_json(p.stdout) is None


def test_unknown_device_kind_is_refused(monkeypatch, tiny_root):
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    cell = spec.load_cell(tiny_root, "tiny.open")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    assert run.open_chip(cell, tiny_root) is None


def test_weights_have_the_programs_layout_and_agree_by_layer():
    """The program's tree (stacked layers, made in one call) has the
    shapes of ``Model.init``, and each layer equals the leaves the
    reference makes for that layer alone."""
    from bench import program
    program.import_program(REPO)
    from repro.models.model import build_model
    s = costs.Shapes.of(TINY_CONFIG)
    got = weights.program_params(9, s)
    want = jax.eval_shape(build_model(program.model_config(TINY_CONFIG))
                          .init, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        assert a.shape == b.shape and a.dtype == b.dtype
    key = weights.seed_key(9)
    one = weights.layer_weights(key, s, 1)
    np.testing.assert_array_equal(got["layers"]["attn"]["wq"][1],
                                  one["wq"])
    np.testing.assert_array_equal(got["layers"]["mlp"]["w_down"][1],
                                  one["w_down"])
    np.testing.assert_array_equal(got["layers"]["ln1"]["scale"][1],
                                  one["attn_norm"])
    wq = np.asarray(one["wq"], np.float32)
    assert np.abs(wq).max() <= 2.0 ** weights._log2_half(s.d)
    assert len(np.unique(wq)) > 1000


def test_whole_run_on_the_cpu(tiny_root, capsys):
    assert run.main(ARGS + ["--trace", "0"], root=tiny_root,
                    require_chip=False) == 0
    out = capsys.readouterr()
    res = _last_json(out.out)
    assert res["correct"] is True
    assert res["attempted"] > 10 and res["failed"] == 0
    assert set(res["metrics"]) == {
        m["name"] for m in spec.load_benchmark(REPO)["end_to_end"]}
    assert {"itl_p50_ms", "output_tok_s", "setup_s"} <= set(res["metrics"])
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "limits"
    assert "[check] correct: True" in out.err.strip().splitlines()[-1]


def test_traced_run_on_the_cpu_reports_no_device_number(tiny_root,
                                                          capsys):
    assert run.main(ARGS + ["--trace", "1"], root=tiny_root,
                    require_chip=False) == 0
    res = _last_json(capsys.readouterr().out)
    assert res["correct"] is True
    # host readings only: a CPU run never reports a device metric
    assert set(res["metrics"]) == {"queue_wait_p90_ms", "batch_occupancy"}
    assert "busy_s" not in res["device"]
    assert not os.path.exists(os.path.join(tiny_root, run.RUN_DIR,
                                           "trace"))


def _fake_run(sent, window=(10.0, 20.0)):
    cell = types.SimpleNamespace(config=TINY_CONFIG, traffic=TINY_TRAFFIC)
    return run.Run(cell=cell, shapes=costs.Shapes.of(TINY_CONFIG),
                   peaks=None, max_slots=4, block_size=16, window=window,
                   sent=sent, ticks=[], admissions=[])


def test_unserved_request_counts_with_its_wait_so_far():
    S = run.Sent
    sent = [S(rid=0, prompt=[], max_new=4, due=9.0, times=[9.5, 10.5]),
            S(rid=1, prompt=[], max_new=4, due=11.0, times=[11.2, 11.4]),
            S(rid=2, prompt=[], max_new=4, due=12.0, times=[]),
            S(rid=3, prompt=[], max_new=4, due=19.0, times=[20.5])]
    r = _fake_run(sent)
    assert [s.rid for s in r.in_window()] == [1, 2, 3]
    v = run.end_to_end(r, setup_s=3.0)
    # waits: 0.2 s, 8 s (unserved: window end - due), 1 s (first token
    # after the window: counted to the window's end)
    assert v["ttft_p90_ms"] == pytest.approx(8000.0)
    assert v["itl_p50_ms"] == pytest.approx(200.0, rel=1e-6)
    assert v["output_tok_s"] == pytest.approx(3 / 10.0)
    assert v["setup_s"] == 3.0


def test_per_layer_readers_on_records():
    sent = [run.Sent(rid=i, prompt=[], max_new=4, due=10.0 + i)
            for i in range(4)]
    r = _fake_run(sent)
    r.admissions = [Admission(rid=i, start=10.5 + i, end=10.6 + i,
                              chunks=[(0, 8)]) for i in range(3)]
    r.ticks = [Tick(start=11.0, end=11.1, contexts=[5, 6]),
               Tick(start=12.0, end=12.1, contexts=[5, 6, 7, 8]),
               Tick(start=25.0, end=25.1, contexts=[1])]
    q = spec.metric_reader(REPO, "queue_wait_p90_ms")(r)
    assert q == pytest.approx(7000.0)          # rid 3: never admitted
    assert spec.metric_reader(REPO, "batch_occupancy")(r) == \
        pytest.approx(75.0)
    for name in ("decode_step_ms", "prefill_chunk_ms",
                 "paged_attn_roofline", "device_idle_share", "step_mfu"):
        assert spec.metric_reader(REPO, name)(r) is None


def test_tiny_root_is_complete(tmp_path):
    root = make_root(tmp_path / "c")
    assert spec.load_cell(root, "tiny.open").chips == 1
