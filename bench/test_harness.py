"""The harness: files found by name, the refusals, one whole run at a
tiny size on the CPU, and the arithmetic of the end-to-end metrics."""
from __future__ import annotations

import glob
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run, spec, weights
from bench.conftest import REPO, TINY_CONFIG, TINY_TRAFFIC, make_root
from bench.program import Admission, Tick

ARGS = ["--workload", "tiny.open", "--seed", str(2**33 + 5),
        "--seconds", "2"]


def _last_json(text: str):
    lines = [x for x in text.strip().splitlines() if x.startswith("{")]
    return json.loads(lines[-1]) if lines else None


CONTRACT = ("program_config", "program_params", "gaps", "hidden_rows",
            "vocab", "decode_tick", "prefill_chunk", "kernel_calls")


def test_each_cell_finds_its_files():
    for w in spec.load_benchmark(REPO)["workloads"]:
        cell = spec.load_cell(REPO, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.metric_reader(REPO, m["name"]))
        assert all(callable(getattr(cell.arch, f)) for f in CONTRACT)
        assert isinstance(cell.arch.SPANS, tuple)


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


@pytest.mark.parametrize("change, error, words", [
    (lambda c: c.pop("architecture"), ValueError, 'no "architecture" key'),
    (lambda c: c.update(architecture="no_such_arch"), FileNotFoundError,
     os.path.join("bench", "arch", "no_such_arch.py")),
], ids=["no key", "no module"])
def test_a_configuration_without_its_architecture_is_refused(
        tmp_path, change, error, words):
    config = json.loads(json.dumps(TINY_CONFIG))
    change(config)
    root = make_root(tmp_path, config)
    with pytest.raises(error, match=re.escape(words)) as e:
        spec.load_cell(root, "tiny.open")
    assert "bench/configs/tiny.json" in str(e.value)


# A second architecture as new files: the dense decoder with its output
# head tied to the embedding, and global leaves of ids of its own.
TIED_ARCH = '''"""The dense GQA decoder with its output head tied to the embedding:
logits are h . embed^T, and the embedding is scaled by sqrt(hidden), as
the program serves a tied dense model. The layers are the dense
decoder's; the table and the final norm have leaf ids of their own."""
import math

import jax
import jax.numpy as jnp

from bench import reference as R
from bench import weights as W
from bench.arch import dense_gqa as D

EMBED, FINAL_NORM = 100, 101

program_config, vocab, SPANS = D.program_config, D.vocab, D.SPANS
decode_tick, prefill_chunk = D.decode_tick, D.prefill_chunk
kernel_calls = D.kernel_calls


def _globals(key, s):
    return (W.leaf(key, EMBED, (s.vocab, s.d), W.EMBED_LOG2),
            W.leaf(key, FINAL_NORM, (s.d,), W.NORM_LOG2))


_make_globals = jax.jit(_globals, static_argnums=1)


def program_params(seed, config):
    s = D.Shapes.of(config)

    def make(key):
        embed, norm = _globals(key, s)
        return {"embed": embed, "final_ln": {"scale": norm},
                "layers": D.program_layers(key, s)}
    return jax.jit(make)(W.seed_key(seed))


def hidden_rows(config, seed, tokens, rows, quant=None):
    s = D.Shapes.of(config)
    emb, _ = _make_globals(W.seed_key(seed), s)
    x = jnp.take(emb, jnp.asarray(R.padded(tokens)), axis=0)
    x = x.astype(jnp.float32) * math.sqrt(s.d)
    return D.run_layers(config, seed, x, quant)[jnp.asarray(rows)]


def gaps(config, seed, sequences, quant=None):
    emb, norm = _make_globals(W.seed_key(seed), D.Shapes.of(config))
    return R.served_gaps(
        sequences, lambda seq, rows, q: hidden_rows(config, seed, seq,
                                                    rows, q),
        norm, emb.T, float(config["rms_norm_eps"]), quant)
'''


def _files(top) -> dict:
    return {os.path.join(dp, f): open(os.path.join(dp, f), "rb").read()
            for dp, _, fs in os.walk(top) for f in fs
            if not f.endswith(".pyc")}


def test_a_new_architecture_is_new_files(tmp_path, capsys):
    """A tiny checkout gains an architecture module, a configuration
    that names it and a cell, and runs it to ``correct``, traced and
    not; no file of the harness changes."""
    root = make_root(tmp_path)
    before = {**_files(spec.BENCH_DIR), **_files(root)}
    with open(os.path.join(root, "bench", "arch", "tied_gqa.py"),
              "w") as f:
        f.write(TIED_ARCH)
    config = dict(TINY_CONFIG, name="tied", architecture="tied_gqa",
                  tie_word_embeddings=True)
    with open(os.path.join(root, "bench", "configs", "tied.json"),
              "w") as f:
        json.dump(config, f)
    bench = spec.load_benchmark(root)
    bench["configs"].append({"name": "tied", "source": "test",
                             "file": "bench/configs/tied.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tied.open", "config": "tied",
                               "traffic": "tiny", "chips": 1,
                               "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = spec.load_cell(root, "tied.open")
    assert cell.arch.__file__ == os.path.join(root, "bench", "arch",
                                              "tied_gqa.py")
    assert run.open_chip(cell, root, require_chip=False)
    tied = cell.arch.program_params(5, cell.config)
    dense = spec.load_cell(root, "tiny.open").arch.program_params(
        5, TINY_CONFIG)
    assert "lm_head" not in tied and "lm_head" in dense
    assert not np.array_equal(tied["embed"], dense["embed"])
    np.testing.assert_array_equal(tied["layers"]["attn"]["wq"],
                                  dense["layers"]["attn"]["wq"])
    args = ["--workload", "tied.open", "--seed", str(2**33 + 17),
            "--seconds", "2"]
    for trace in ("0", "1"):
        assert run.main(args + ["--trace", trace], root=root,
                        require_chip=False) == 0
        res = _last_json(capsys.readouterr().out)
        assert res["correct"] is True, res["limits"]
        assert res["attempted"] > 10 and res["failed"] == 0
    after = {**_files(spec.BENCH_DIR), **_files(root)}
    # BENCHMARK.json gains the new entries; every other file is as it was
    del before[os.path.join(root, "BENCHMARK.json")]
    assert {k: after[k] for k in before} == before


# A second named kernel and a counter carried on a span, as new files: an
# architecture whose step also calls a routed-expert kernel, named
# ``expert_matmul`` in the program, and whose program writes a
# ``moe.route`` span with the tokens it routed; and a metric for each.
MOE_ARCH = '''"""The dense GQA decoder with a routed-expert kernel."""
from bench.arch import dense_gqa as D

program_config, program_params, vocab = D.program_config, \\
    D.program_params, D.vocab
gaps, hidden_rows = D.gaps, D.hidden_rows
decode_tick, prefill_chunk = D.decode_tick, D.prefill_chunk
SPANS = ("moe.route",)


def kernel_calls(config, contexts, queries, block):
    s = D.Shapes.of(config)
    n = len(contexts) * queries
    expert = ("expert_matmul", 6 * n * s.d * s.ff, 2 * 3 * s.d * s.ff)
    return D.kernel_calls(config, contexts, queries, block) \\
        + [expert] * s.layers
'''
MOE_METRICS = {
    "expert_matmul_roofline": '''from bench import trace


def read(run):
    return trace.roofline(run, "expert_matmul")
''',
    "routed_tokens": '''def read(run):
    routes = [s for s in run.trace.spans if s.name == "moe.route"] \\
        if run.trace else []
    if not routes:
        return None
    return sum(s.attrs["tokens"] for s in routes) / len(routes)
'''}


def test_a_new_kernel_and_span_attribute_are_new_files(tmp_path):
    """A dropped-in architecture names a second kernel and a span; the
    profiler's record of that span keeps its attribute through the
    harness's reduction, and dropped-in metric files read the kernel's
    roofline share and the attribute. No file of the harness changes."""
    from bench import costs, trace
    root = make_root(tmp_path / "c")
    before = {**_files(spec.BENCH_DIR), **_files(root)}
    with open(os.path.join(root, "bench", "arch", "moe_gqa.py"), "w") as f:
        f.write(MOE_ARCH)
    for name, text in MOE_METRICS.items():
        with open(os.path.join(root, "bench", "metrics", name + ".py"),
                  "w") as f:
            f.write(text)
    with open(os.path.join(root, "bench", "configs", "moe.json"), "w") as f:
        json.dump(dict(TINY_CONFIG, name="moe", architecture="moe_gqa"), f)
    bench = spec.load_benchmark(root)
    bench["configs"].append({"name": "moe", "source": "test",
                             "file": "bench/configs/moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "moe.open", "config": "moe",
                               "traffic": "tiny", "chips": 1,
                               "why": "test"})
    bench["per_layer"] += [
        {"name": n, "unit": u, "better": "higher", "source": "device_trace",
         "layer": "kernels", "moves": "itl_p50_ms",
         "workloads": ["moe.open"]}
        for n, u in (("expert_matmul_roofline", "%"),
                     ("routed_tokens", "tokens"))]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = spec.load_cell(root, "moe.open")

    # the spans as this host's profiler records them, the harness's
    # capture around them
    cap = trace.Capture(str(tmp_path / "trace"))
    cap.start()
    try:
        with cap.span():
            for tokens in (12, 20):
                with jax.profiler.TraceAnnotation("moe.route",
                                                  tokens=tokens):
                    time.sleep(0.005)
            with jax.profiler.TraceAnnotation("moe.unnamed", tokens=99):
                pass
            time.sleep(0.04)
    finally:
        cap.stop()
    path, = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    ev = trace.extract(path, trace.SPANS + cell.arch.SPANS)
    assert [(x[0], x[3]) for x in ev["spans"] if x[0] != trace.CAPTURE] \
        == [("moe.route", {"tokens": 12}), ("moe.route", {"tokens": 20})]
    # a tick's device work, made by hand, in the capture's last 30 ms
    (_, c0, cd, _), = [x for x in ev["spans"] if x[0] == trace.CAPTURE]
    t = c0 + cd - 30 * 1_000_000
    ms = 1_000_000
    ev["spans"].append((trace.TICK, t, 25 * ms, {"index": 4, "active": 2}))
    ev["modules"] = [("jit_decode_tick(1)", t + ms, 20 * ms)]
    ev["ops"] = [("%paged_attention.3", t + ms, 4 * ms),
                 ("%fusion.7", t + 5 * ms, 2 * ms),     # the consumer
                 ("%expert_matmul.1", t + 7 * ms, 6 * ms),
                 ("%fusion.8", t + 13 * ms, 8 * ms)]
    r = types.SimpleNamespace(
        cell=cell, ticks=[Tick(0.0, 0.1, [100, 200], 4)], admissions=[],
        block_size=16, peaks=costs.load_peaks("TPU v5 lite"))
    r.trace = trace.summarize(ev, r)
    names = ("routed_tokens", "expert_matmul_roofline",
             "paged_attn_roofline", "tick_programs")
    assert set(names) <= {m["name"] for m in cell.per_layer}
    read = {m: spec.metric_reader(root, m)(r) for m in names}
    work = cell.arch.kernel_calls(cell.config, [100, 200], 1, 16)
    least = {k: sum(costs.min_seconds(f, b, r.peaks)[0]
                    for n, f, b in work if n == k)
             for k in ("paged_attention", "expert_matmul")}
    assert read["routed_tokens"] == 16.0
    assert read["expert_matmul_roofline"] == pytest.approx(
        100 * least["expert_matmul"] / 0.006)
    assert read["paged_attn_roofline"] == pytest.approx(
        100 * least["paged_attention"] / 0.004)
    assert read["tick_programs"] == 1
    after = {**_files(spec.BENCH_DIR), **_files(root)}
    del before[os.path.join(root, "BENCHMARK.json")]
    assert {k: after[k] for k in before} == before


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
    arch = spec.load_arch(REPO, TINY_CONFIG)
    s = arch.Shapes.of(TINY_CONFIG)
    got = arch.program_params(9, TINY_CONFIG)
    want = jax.eval_shape(build_model(arch.program_config(TINY_CONFIG))
                          .init, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        assert a.shape == b.shape and a.dtype == b.dtype
    key = weights.seed_key(9)
    one = arch.layer_weights(key, s, 1)
    np.testing.assert_array_equal(got["layers"]["attn"]["wq"][1],
                                  one["wq"])
    np.testing.assert_array_equal(got["layers"]["mlp"]["w_down"][1],
                                  one["w_down"])
    np.testing.assert_array_equal(got["layers"]["ln1"]["scale"][1],
                                  one["attn_norm"])
    wq = np.asarray(one["wq"], np.float32)
    assert np.abs(wq).max() <= 2.0 ** weights._log2_half(s.d)
    assert len(np.unique(wq)) > 1000


def _digest(items) -> str:
    h = hashlib.sha256()
    for name, a in items:
        a = np.asarray(a)
        h.update(f"{name}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


# what the harness made and read before the dense decoder became an
# architecture module: the program's tree, the reference's leaves (both
# exact), and the reference's gaps over one sequence (float32 on the CPU)
PROGRAM_DIGEST = \
    "f4721efea778a16f2d9ca48d17bb891db862414e7ff04bee7b15b7f8a865df93"
REFERENCE_DIGEST = \
    "157b223ae6ab7a6ed61b85cf9454868db6158728c9ba4c6536eb966c34857234"
GAPS = [4.085655689239502, 3.379556179046631, 2.0903677940368652,
        1.4823611974716187, 2.393129587173462, 0.8508382439613342,
        4.324252605438232, 2.7676799297332764, 1.676171898841858,
        4.041431427001953, 0.6789654493331909, 1.7910741567611694,
        1.5336527824401855, 2.633242130279541, 2.7158641815185547]


def test_dense_weights_and_reference_are_the_ones_made_before():
    arch = spec.load_arch(REPO, TINY_CONFIG)
    s = arch.Shapes.of(TINY_CONFIG)
    flat = jax.tree_util.tree_flatten_with_path(
        arch.program_params(9, TINY_CONFIG))[0]
    assert _digest((jax.tree_util.keystr(k), v) for k, v in flat) == \
        PROGRAM_DIGEST
    key = weights.seed_key(9)
    items = [(n, arch._make_global(key, s=s, name=n))
             for n in ("embed", "final_norm", "lm_head")]
    for layer in range(s.layers):
        w = arch._make_layer(key, jnp.int32(layer), s=s)
        items += [(f"{layer}.{n}", w[n]) for n in sorted(w)]
    assert _digest(items) == REFERENCE_DIGEST
    toks = [(7 * i + 3) % 500 + 3 for i in range(45)]
    np.testing.assert_allclose(
        arch.gaps(TINY_CONFIG, 9, [(toks[:30], toks[30:])]), GAPS,
        rtol=1e-5)


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
    return run.Run(cell=cell, peaks=None, max_slots=4, block_size=16,
                   window=window, sent=sent, ticks=[], admissions=[])


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
                              chunks=[(0, 8)], tick=i) for i in range(3)]
    r.ticks = [Tick(start=11.0, end=11.1, contexts=[5, 6], index=0),
               Tick(start=12.0, end=12.1, contexts=[5, 6, 7, 8], index=1),
               Tick(start=25.0, end=25.1, contexts=[1], index=2)]
    q = spec.metric_reader(REPO, "queue_wait_p90_ms")(r)
    assert q == pytest.approx(7000.0)          # rid 3: never admitted
    assert spec.metric_reader(REPO, "batch_occupancy")(r) == \
        pytest.approx(75.0)
    for name in ("decode_step_ms", "prefill_chunk_ms",
                 "paged_attn_roofline", "device_idle_share", "step_mfu",
                 "tick_host_ms", "admit_host_ms", "tick_programs"):
        assert spec.metric_reader(REPO, name)(r) is None


def test_tiny_root_is_complete(tmp_path):
    root = make_root(tmp_path / "c")
    assert spec.load_cell(root, "tiny.open").chips == 1
