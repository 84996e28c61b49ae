"""Smoke run of the serving main path on a TPU v5e.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # 1x4 TP engine and 2x2 router
                                     # (fused, disaggregated) against
                                     # the one-chip engine

Model: qwen2.5-14b at its published widths — hidden 5120, 40 query and
8 KV heads of 128, ff 13824, the full 152,064-row vocabulary, QKV bias,
RoPE theta 1e6 — with random weights from a seed. Only depth is cut, to
8 of 48 layers: 48 layers are about 29.5 GB in bf16, more than a 16 GB
chip holds; 8 layers are 3.76 B parameters, 7.5 GB.

One chip, three phases, each through the entry points a user calls
(``Engine`` as ``repro.launch.serve`` builds it, default plan: the
``standard`` backend, the kv bf16 paged pool, the block-streamed Pallas
kernel):

  1. serve 8 greedy requests, 32 new tokens each, prompts of 5 to 200
     tokens (four need two or more 64-token prefill chunks), from a
     1 GiB pool;
  2. replay the same requests through the gather oracle (plain XLA,
     ``decode_schedule="gather"``, float32 contractions at full
     precision), teacher-forced on the streamed tokens, and compare
     every sampled row of logits and every greedy choice;
  3. run each served Pallas kernel natively against its jnp twin:
     ``paged_attend`` (kv pool, n = 1 and n = 64) and ``wqk_score`` at
     whisper-tiny's encoder shape.

Give ``--chips 4`` an empty ``JAX_COMPILATION_CACHE_DIR``: runs that
loaded multi-chip programs from a cache carried between machines halted
the chip (PERF.md).

The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``. The script exits non-zero, without
that line, when JAX finds no TPU or when any phase fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

import numpy as np

LAYERS = 8
SEED = 0
PROMPT_LENS = (5, 17, 40, 64, 65, 100, 150, 200)
MAX_NEW = 32
MAX_LEN = 256
BLOCK = 16
POOL_BYTES = 1 << 30           # 32,768 tokens at 32 KiB per token
# Logits may differ by LOGIT_BOUND times their rms. Both schedules run
# the same float32 attention, but sum in different orders, so some of
# the bf16 roundings of each layer's outputs land one ulp (2^-8
# relative) apart, and the logits move by a few ulps of their scale.
# 2^-3 allows 32 such ulps; a wrong block, mask or head mapping moves
# logits by O(rms). Where two engines pick different greedy tokens, the
# other's choice must lie within the same bound of the row's maximum.
LOGIT_BOUND = 2 ** -3
# Pallas paged kernel vs its jnp twin: the twin runs at full float32
# precision; the kernel may round its probabilities to bf16 once on the
# MXU (2^-9 relative), so |out - twin| <= 2^-8 * max|v|.
PAGED_BOUND = 2 ** -8
# wqk_score vs its twin: the same integer G contracted in float32 in a
# different order; 1e-3 of the score range admits any summation order.
WQK_BOUND = 1e-3


def config():
    from repro.configs.base import get_arch
    return dataclasses.replace(get_arch("qwen2.5-14b"), num_layers=LAYERS)


def build_params(model):
    import jax
    return jax.jit(model.init)(jax.random.PRNGKey(SEED))


def requests(vocab: int):
    from repro.serving.engine import Request
    rng = np.random.default_rng(SEED)
    return [Request(rid=i, tokens=[1] + rng.integers(3, vocab, n - 1)
                    .tolist(), max_new_tokens=MAX_NEW, eos_id=None)
            for i, n in enumerate(PROMPT_LENS)]


class Tape:
    """Every sampled row of logits and its greedy token, keyed by
    (request id, token index), across all engines it is attached to.
    With ``replay`` set, the engines sample the replayed tape's tokens
    instead of their own (teacher forcing), so a comparison never
    follows two diverging token paths."""

    def __init__(self, replay: Tape | None = None):
        self.replay = replay
        self.rows: dict[tuple[int, int], np.ndarray] = {}
        self.tokens: dict[tuple[int, int], int] = {}

    def attach(self, engine):
        sample = engine._sample

        def taped(logits, reqs):
            own = sample(logits, reqs)
            rows = np.asarray(logits, np.float32)
            out = np.array(own)
            for i, r in enumerate(reqs):
                if r is None:
                    continue
                key = (r.rid, len(r.output))
                self.rows[key] = rows[i]
                self.tokens[key] = int(own[i])
                if self.replay is not None:
                    out[i] = self.replay.tokens[key]
            return out
        engine._sample = taped

    def attach_all(self, engines):
        for e in engines:
            self.attach(e)


def compare(ref: Tape, got: Tape) -> dict:
    """``got`` was teacher-forced on ``ref``'s tokens. Per row: the
    largest logit difference against LOGIT_BOUND * rms; greedy choices
    equal, or ``ref``'s choice within the bound of ``got``'s maximum."""
    unmatched = len(ref.rows.keys() ^ got.rows.keys())
    worst = worst_rel = 0.0
    same = near = wrong = 0
    for key in ref.rows.keys() & got.rows.keys():
        a, b = ref.rows[key], got.rows[key]
        bound = LOGIT_BOUND * float(np.sqrt(np.mean(a * a)))
        d = float(np.max(np.abs(a - b)))
        worst, worst_rel = max(worst, d), max(worst_rel, d / bound)
        t_ref, t_got = ref.tokens[key], got.tokens[key]
        if t_ref == t_got:
            same += 1
        elif b[t_got] - b[t_ref] <= bound:
            near += 1
        else:
            wrong += 1
    return {"rows": len(ref.rows), "unmatched": unmatched,
            "max_abs_dlogit": worst, "max_dlogit_over_bound": worst_rel,
            "greedy_equal": same, "greedy_near_tie": near,
            "greedy_wrong": wrong,
            "ok": worst_rel <= 1.0 and wrong == 0 and unmatched == 0}


def _engine_kw():
    return dict(max_slots=len(PROMPT_LENS), max_len=MAX_LEN,
                block_size=BLOCK, hbm_bytes=POOL_BYTES)


def _bytes_in_use() -> str:
    import jax
    return " ".join(f"{(d.memory_stats() or {}).get('bytes_in_use', 0)}"
                    for d in jax.devices())


def _report(label: str, res: dict) -> bool:
    print(f"[parity] {label}: {res['rows']} sampled rows "
          f"({res['unmatched']} unmatched); max |dlogit| "
          f"{res['max_abs_dlogit']} = {res['max_dlogit_over_bound']} of "
          f"the bound ({LOGIT_BOUND} x rms); greedy equal "
          f"{res['greedy_equal']}, near-tie {res['greedy_near_tie']}, "
          f"wrong {res['greedy_wrong']} -> "
          f"{'ok' if res['ok'] else 'FAILED'}", flush=True)
    return res["ok"]


def serve_phase(cfg) -> bool:
    """Streamed engine, then the gather oracle on the same requests."""
    import jax

    from repro.models.model import build_model
    from repro.serving.engine import Engine, Request

    model = build_model(cfg)
    t = time.perf_counter()
    params = build_params(model)
    jax.block_until_ready(params)
    print(f"[serve] random weights (seed {SEED}) in "
          f"{time.perf_counter() - t:.1f} s", flush=True)

    eng = Engine(model, params, **_engine_kw())
    print(f"[serve] plan: backend {eng.plan.backend.name!r}, cache "
          f"{eng.plan.cache_mode!r} {cfg.dtype}, decode schedule "
          f"{eng.decode_schedule!r}; {eng.allocator.num_usable} blocks x "
          f"{BLOCK} tokens = {eng.pool_bytes_per_device():,} B pool; "
          f"prefill chunk {eng.prefill_chunk}", flush=True)
    t = time.perf_counter()
    eng.run([Request(rid=-1, tokens=[1] * (eng.prefill_chunk + 6),
                     max_new_tokens=2, eos_id=None)])
    print(f"[serve] compile + warm-up (prefill chunk and decode tick "
          f"graphs): {time.perf_counter() - t:.1f} s", flush=True)
    with eng._mesh_ctx():
        tick = eng._decode_tick.lower(
            eng.params, eng.pool, eng._dev(eng.tables),
            eng._dev(np.zeros((eng.max_slots, 1), np.int32)),
            eng._dev(eng.pos), eng._blocks_used(eng.pos)).as_text()
    kernel_in_tick = "tpu_custom_call" in tick
    print(f"[serve] Pallas paged kernel (tpu_custom_call) in the decode "
          f"tick: {kernel_in_tick}", flush=True)

    tape = Tape()
    tape.attach(eng)
    reqs = requests(cfg.vocab_size)
    t = time.perf_counter()
    eng.run(reqs)
    dt = time.perf_counter() - t
    n_tok = sum(len(r.output) for r in reqs)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[serve] {len(reqs)} requests, prompts {list(PROMPT_LENS)}, "
          f"{n_tok} tokens in {dt:.2f} s, {eng.ticks} ticks: "
          f"{n_tok / dt:.1f} tokens/s (smoke figure, not a benchmark); "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}",
          flush=True)
    ok = kernel_in_tick and all(
        len(r.output) == MAX_NEW and r.finish_reason == "length"
        for r in reqs)
    del eng
    gc.collect()

    # the oracle's float32 contractions (scores, probabilities x V) run
    # at full precision; XLA's default on a TPU rounds them to bf16
    oracle = Engine(model, params, decode_schedule="gather",
                    **_engine_kw())
    got = Tape(replay=tape)
    got.attach(oracle)
    with jax.default_matmul_precision("highest"):
        oracle.run(requests(cfg.vocab_size))
    return _report("streamed (Pallas) vs gather oracle (XLA)",
                   compare(tape, got)) and ok


def kernel_phase() -> bool:
    """Each served Pallas kernel natively against its jnp twin."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_attention import paged_attend
    from repro.kernels.wqk_score import ops as wqk

    rng = np.random.default_rng(SEED)
    H, Hkv, dh, NB, nbk = 40, 8, 128, 2048, MAX_LEN // BLOCK
    ok = True
    k_pool = jnp.asarray(rng.standard_normal((NB, BLOCK, Hkv, dh)),
                         jnp.bfloat16)
    v_pool = jnp.asarray(rng.standard_normal((NB, BLOCK, Hkv, dh)),
                         jnp.bfloat16)
    for n, B in ((1, 8), (64, 1)):
        q = jnp.asarray(rng.standard_normal((B, H, n, dh)),
                        jnp.bfloat16).astype(jnp.float32)
        tables = jnp.asarray(rng.permutation(np.arange(1, NB))
                             [:B * nbk].reshape(B, nbk), jnp.int32)
        last = rng.integers(n - 1, MAX_LEN, B)
        qpos = jnp.asarray(last[:, None] - n + 1 + np.arange(n), jnp.int32)
        used = jnp.asarray(last // BLOCK + 1, jnp.int32)
        args = (q, k_pool, tables, used, qpos)
        kw = dict(v_pool=v_pool, scale=1 / math.sqrt(dh))
        out = paged_attend(*args, impl="pallas", interpret=False, **kw)
        with jax.default_matmul_precision("highest"):
            twin = paged_attend(*args, impl="jnp", **kw)
        err = float(jnp.max(jnp.abs(out - twin)))
        bound = PAGED_BOUND * float(jnp.max(jnp.abs(v_pool)))
        good = err <= bound and bool(jnp.all(jnp.isfinite(out)))
        ok &= good
        print(f"[kernel] paged_attend kv bf16, n={n}, B={B}: max "
              f"|pallas - jnp| {err} (bound {bound}) -> "
              f"{'ok' if good else 'FAILED'}", flush=True)

    N, D, heads = 1500, 385, 6               # whisper-tiny encoder, D_aug
    xq = jnp.asarray(rng.standard_normal((1, N, D)), jnp.float32)
    xk = jnp.asarray(rng.standard_normal((1, N, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((heads, D, D)), jnp.float32)
    s = wqk.scores(xq, xk, w)
    twin = wqk.scores_jnp(xq, xk, w)
    rel = float(jnp.max(jnp.abs(s - twin)) / jnp.max(jnp.abs(twin)))
    good = rel <= WQK_BOUND and s.shape == (1, heads, N, N)
    ok &= good
    print(f"[kernel] wqk_score N={N} D_aug={D} H={heads}: max |pallas - "
          f"jnp| / max|S| {rel} (bound {WQK_BOUND}) -> "
          f"{'ok' if good else 'FAILED'}", flush=True)
    return ok


def mesh_phase(cfg) -> bool:
    """The one-chip engine first (its outputs kept on the host only),
    then the 1x4 TP engine and the 2x2 router, fused and disaggregated,
    each teacher-forced on the one-chip tokens and freed before the
    next is built."""
    import jax

    from repro.launch.mesh import parse_mesh
    from repro.models.model import build_model
    from repro.serving.engine import Engine
    from repro.serving.router import ReplicaRouter

    model = build_model(cfg)
    params = build_params(model)
    kw = _engine_kw()
    ref = Engine(model, params, **kw)
    tape = Tape()
    tape.attach(ref)
    ref.run(requests(cfg.vocab_size))
    print(f"[mesh] one-chip reference: {len(tape.rows)} sampled rows",
          flush=True)
    host_params = jax.device_get(params)
    del ref, params
    gc.collect()
    print(f"[mesh] bytes in use per device after freeing it: "
          f"{_bytes_in_use()}", flush=True)

    builds = (
        ("1x4 TP engine", lambda: Engine(
            model, host_params, mesh=parse_mesh("1x4"), **kw)),
        ("2x2 router, fused", lambda: ReplicaRouter.for_mesh(
            model, host_params, parse_mesh("2x2"), **kw)),
        ("2x2 router, disaggregated", lambda: ReplicaRouter.for_mesh(
            model, host_params, parse_mesh("2x2"), disaggregate=True,
            **kw)),
    )
    ok = True
    for label, build in builds:
        eng = build()
        engines = getattr(eng, "engines", [eng])
        jax.block_until_ready([(e.params, e.pool) for e in engines])
        print(f"[mesh] {label}: {len(engines)} engine(s) built; bytes in "
              f"use per device {_bytes_in_use()}", flush=True)
        got = Tape(replay=tape)
        got.attach_all(engines)
        t = time.perf_counter()
        eng.run(requests(cfg.vocab_size))
        print(f"[mesh] {label}: ran in {time.perf_counter() - t:.1f} s "
              f"(compile included)", flush=True)
        ok &= _report(f"{label} vs one chip", compare(tape, got))
        del eng, engines
        gc.collect()
        print(f"[mesh] bytes in use per device after freeing it: "
              f"{_bytes_in_use()}", flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 1x4 TP engine and the 2x2 "
                         "router against the one-chip engine")
    args = ap.parse_args(argv)

    import jax

    from repro import util
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX finds "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    print(f"[chip_smoke] compile cache: {util.enable_compile_cache()}")
    print(f"[chip_smoke] device {dev.device_kind!r} x {len(devices)}; "
          f"jax {jax.__version__}", flush=True)
    cfg = config()
    print(f"[chip_smoke] model qwen2.5-14b at published widths: hidden "
          f"{cfg.d_model}, {cfg.num_heads} query / {cfg.num_kv_heads} KV "
          f"heads of {cfg.head_dim}, ff {cfg.d_ff}, vocabulary "
          f"{cfg.vocab_size}, qkv_bias {cfg.qkv_bias}, rope theta "
          f"{cfg.rope_theta}, {cfg.dtype}; depth cut to {cfg.num_layers} "
          f"of 48 layers ({cfg.param_count() / 1e9:.2f} B params): 48 "
          f"layers are ~29.5 GB in bf16, beyond a 16 GB chip", flush=True)

    if args.chips == 4:
        ok = mesh_phase(cfg)
    else:
        ok = serve_phase(cfg)
        ok = kernel_phase() and ok
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "src"))
    sys.exit(main())
