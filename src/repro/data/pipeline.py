"""Deterministic, stateless-resumable synthetic LM data pipeline.

Design constraints (DESIGN.md §5):
  * **Stateless resume** — batch contents are a pure function of
    ``(seed, step)``; restarting from a checkpoint at step k regenerates
    exactly the stream from step k with no iterator state to persist.
  * **Sharded** — each data-parallel host slices its rows of the global
    batch from the same deterministic stream (``host_slice``).
  * **Padding-aware** — emits ``loss_mask`` and per-sequence lengths; the
    zero-padding structure is exactly the input sparsity the paper's
    zero-skip mechanism exploits (§III.C), so the pipeline also reports
    pad fractions (``pad_fraction``).
  * **Packing** — optional sequence packing removes pad waste; this is
    the TPU-friendly analogue of the macro's token-level zero skipping
    (documented in core/zeroskip.py).

Synthetic text: a Zipf-distributed token-ngram Markov stream — cheap,
deterministic, and with realistic low-frequency-token statistics (the
paper's argument for zero-rich embeddings).
"""
from __future__ import annotations

import dataclasses
from collections.abc import Iterator

import numpy as np

from repro.data.tokenizer import BOS_ID, EOS_ID, PAD_ID


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    pack: bool = True            # sequence packing (no pad waste)
    mean_doc_len: int = 512      # geometric document lengths
    zipf_a: float = 1.2          # token frequency skew


def _philox(seed: int, step: int, rows: int, cols: int) -> np.random.Generator:
    """Counter-based RNG: independent stream per (seed, step)."""
    return np.random.default_rng(np.random.SeedSequence([seed, step]))


def _doc_stream(rng: np.random.Generator, cfg: DataConfig, n_tokens: int
                ) -> np.ndarray:
    """One row of Zipf-Markov synthetic tokens with document boundaries."""
    out = np.empty(n_tokens, np.int64)
    pos = 0
    v = cfg.vocab_size
    while pos < n_tokens:
        dlen = min(1 + rng.geometric(1.0 / cfg.mean_doc_len), n_tokens - pos)
        # Zipf over the vocab, shifted past specials
        toks = rng.zipf(cfg.zipf_a, size=dlen)
        toks = (toks - 1) % max(v - 3, 1) + 3
        toks[0] = BOS_ID
        if pos + dlen < n_tokens:
            toks[-1] = EOS_ID
        out[pos:pos + dlen] = toks
        pos += dlen
    return out


def make_batch(cfg: DataConfig, step: int) -> dict[str, np.ndarray]:
    """The global batch for ``step`` — pure function of (cfg.seed, step).

    Returns tokens/labels (B, S) int32, loss_mask (B, S) f32,
    lengths (B,) int32. Unpacked mode pads ragged docs with PAD_ID
    (zero) — the paper's zero-rich regime; packed mode fills fully.
    """
    B, S = cfg.global_batch, cfg.seq_len
    rng = _philox(cfg.seed, step, B, S)
    tokens = np.empty((B, S + 1), np.int64)
    lengths = np.full((B,), S, np.int32)
    if cfg.pack:
        for b in range(B):
            tokens[b] = _doc_stream(rng, cfg, S + 1)
    else:
        for b in range(B):
            dlen = min(1 + rng.geometric(1.0 / cfg.mean_doc_len), S)
            row = np.full(S + 1, PAD_ID, np.int64)
            row[:dlen + 1] = _doc_stream(rng, cfg, dlen + 1)
            tokens[b] = row
            lengths[b] = dlen
    inp = tokens[:, :-1].astype(np.int32)
    lab = tokens[:, 1:].astype(np.int32)
    mask = (lab != PAD_ID).astype(np.float32)
    return {"tokens": inp, "labels": lab, "loss_mask": mask,
            "lengths": lengths}


def host_slice(batch: dict[str, np.ndarray], host_id: int,
               n_hosts: int) -> dict[str, np.ndarray]:
    """Rows of the global batch owned by ``host_id`` (data-parallel I/O)."""
    B = batch["tokens"].shape[0]
    assert B % n_hosts == 0, (B, n_hosts)
    per = B // n_hosts
    sl = slice(host_id * per, (host_id + 1) * per)
    return {k: v[sl] for k, v in batch.items()}


def pad_fraction(batch: dict[str, np.ndarray]) -> float:
    """Fraction of positions that are pure zero padding (zero-skip's
    token-level component)."""
    return float(1.0 - batch["loss_mask"].mean())


class DataIterator:
    """Stateless iterator facade: ``DataIterator(cfg, start_step)`` resumes
    mid-stream with no persisted state beyond the step counter."""

    def __init__(self, cfg: DataConfig, start_step: int = 0,
                 host_id: int = 0, n_hosts: int = 1):
        self.cfg = cfg
        self.step = start_step
        self.host_id, self.n_hosts = host_id, n_hosts

    def __iter__(self) -> Iterator[dict[str, np.ndarray]]:
        return self

    def __next__(self) -> dict[str, np.ndarray]:
        b = make_batch(self.cfg, self.step)
        self.step += 1
        if self.n_hosts > 1:
            b = host_slice(b, self.host_id, self.n_hosts)
        return b
