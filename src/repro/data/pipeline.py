"""Synthetic token/embedding pipeline.

Deterministic per (seed, step) so that restarts and elastic resizes can
replay the exact stream — a restart after an SS shrink (or a failure)
resumes mid-epoch losslessly, which the integration tests assert.
"""
from __future__ import annotations

import queue
import threading
from dataclasses import dataclass
from typing import Iterator

import jax
import numpy as np
from jax.sharding import NamedSharding

from repro.models.common import ModelConfig
from repro.parallel.sharding import ShardingContext, resolve_spec


@dataclass
class SyntheticTokens:
    """Zipf-ish synthetic LM stream with next-token labels."""

    cfg: ModelConfig
    batch: int
    seq: int
    seed: int = 0

    def sample(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed << 20) ^ step)
        # Zipf-like marginal over the vocab (heavier head, realistic gather
        # locality for the embedding table).
        v = self.cfg.vocab
        ranks = rng.zipf(1.3, size=(self.batch, self.seq + 1)).astype(np.int64)
        tokens = np.minimum(ranks - 1, v - 1).astype(np.int32)
        out = {
            "labels": tokens[:, 1:],
        }
        if self.cfg.embed_inputs:
            erng = np.random.default_rng((self.seed << 21) ^ step)
            out["embeds"] = erng.standard_normal(
                (self.batch, self.seq, self.cfg.d_model), dtype=np.float32
            )
        else:
            out["tokens"] = tokens[:, :-1]
        if self.cfg.mrope_sections:
            pos = np.broadcast_to(
                np.arange(self.seq, dtype=np.int32), (self.batch, self.seq)
            )
            out["positions"] = np.stack([pos, pos, pos])
        return out

    def iter(self, start_step: int = 0, prefetch: int = 2) -> Iterator[dict]:
        """Background-thread prefetching iterator.

        An exception in the worker is handed through the queue and raised
        in the consumer, which would otherwise wait forever.
        """
        q: queue.Queue = queue.Queue(maxsize=prefetch)
        stop = threading.Event()

        def worker():
            s = start_step
            try:
                while not stop.is_set():
                    q.put(self.sample(s))
                    s += 1
            except Exception as e:
                q.put(e)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if isinstance(item, Exception):
                    raise item
                yield item
        finally:
            stop.set()


def batch_spec(cfg: ModelConfig, ctx: ShardingContext) -> dict:
    """NamedShardings for each batch field under the context's rules."""
    def spec_for(name: str, ndim: int):
        if name == "positions" and cfg.mrope_sections:
            axes = (None, "batch", "seq")
        elif name == "embeds":
            axes = ("batch", "seq", "embed")
        else:
            axes = ("batch", "seq")
        return axes[:ndim] if ndim else axes

    names = {"labels": 2}
    if cfg.embed_inputs:
        names["embeds"] = 3
    else:
        names["tokens"] = 2
    if cfg.mrope_sections:
        names["positions"] = 3
    return names, spec_for


def make_batch_on_mesh(host_batch: dict, cfg: ModelConfig, ctx: ShardingContext) -> dict:
    """device_put a host batch with the right activation shardings."""
    _, spec_for = batch_spec(cfg, ctx)
    out = {}
    for k, v in host_batch.items():
        axes = spec_for(k, v.ndim)
        spec = resolve_spec(tuple(axes), v.shape, ctx, "act")
        out[k] = jax.device_put(v, NamedSharding(ctx.mesh, spec))
    return out
