"""Tensor-parallel serving on the JCCL fabric.

``TPServeEngine`` shards a :class:`~repro.serving.engine.ServeEngine`
across the ranks of a :class:`~repro.collectives.JcclWorld`. Every rank
runs the SAME jitted compute as the single-host engine (replicated
parameters, deterministic XLA), so the model math is byte-identical to
the reference by construction; what the fabric adds — and what a rail
fault can therefore corrupt — is the data movement between the shards:

* **logits all-gather** — each rank owns a contiguous vocab slice
  (``JcclWorld.shard_bounds``); the full logits vector is reassembled
  over the fabric every step and sampling consumes the *reconstructed*
  bytes, never the local copy. A lost/duplicated/misordered chunk shows
  up as a wrong token, not a silent pass.
* **per-layer activation all-gathers** — the K/V rows each decode step
  appends to the cache are gathered layer-by-layer (one concurrent work
  per layer, mirroring megatron-style per-layer activation sync) and
  byte-verified against the locally computed rows.

A MoE model's expert layer computes the experts it holds
(``ModelConfig.held_experts``) with no exchange: the all-to-all of a
layer split over chips needs a rank per chip, which the ranks here,
sharing one device, are not.

All of a step's works are issued before any is waited on, so a scenario
fault lands while several collectives are in flight and SHIFT's
per-QP masking + the channel scheduler's resteering are both on the
hot path. ``world=None`` degenerates to pure local compute — that mode
IS the byte-identity reference the campaign compares against.

Continuous batching (``start_batch`` / ``admit`` / ``decode_batch``)
gives the request scheduler slot-level admission: a prompt is prefilled
alone, its K/V spliced into a persistent slot cache with per-sequence
lengths (``prompt_lens`` machinery from the ragged-serving fix), and
decode advances all active slots in one batched step. Free slots decode
don't-care rows; because the reference run executes the identical
schedule, those rows are deterministic and never read.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.models import LM

from .engine import ServeEngine


def _bytes_of(a: np.ndarray) -> np.ndarray:
    """Flat uint8 view of an array's bytes (copy-free when contiguous)."""
    return np.ascontiguousarray(a).reshape(-1).view(np.uint8)


@jax.jit
def _new_kv_rows(k, v, prev_len):
    """Each sequence's cache row at ``prev_len`` (a scalar is every
    sequence's), clipped to [0, S-1]: (L, 2, B, KVh, hd), K before V,
    from (L, B, S, KVh, hd) caches."""
    B, S = k.shape[1], k.shape[2]
    at = jnp.clip(jnp.broadcast_to(prev_len, (B,)), 0, S - 1)
    slots = jnp.arange(B)
    return jnp.stack([k[:, slots, at], v[:, slots, at]], axis=1)


class TPServeEngine:
    """Rank-sharded serving engine over a ``JcclWorld`` (or local-only).

    ``local`` lets callers share one jitted :class:`ServeEngine` across
    many TP engines (the campaign runs one engine per scenario cell;
    re-jitting per cell would dominate wall time). ``timeout`` bounds
    every fabric wait in virtual seconds.

    ``reconstruction_mismatches`` counts fabric reconstructions whose
    bytes differed from the locally computed truth — the payload-level
    corruption metric the campaign invariants gate on. ``sync_rounds``
    counts fabric synchronization points (one per prefill/decode step).
    """

    def __init__(self, model: LM, params, world=None, max_len: int = 256,
                 timeout: float = 120.0,
                 local: Optional[ServeEngine] = None):
        if model.cfg.family not in ("dense", "audio", "moe"):
            raise ValueError(
                f"tensor-parallel serving requires a KV-cache family "
                f"(dense/audio/moe), not {model.cfg.family!r}")
        self.model = model
        self.params = params
        self.max_len = max_len
        self.world = world
        self.timeout = timeout
        self._local = local if local is not None else ServeEngine(
            model, params, max_len=max_len)
        if self._local.max_len != max_len:
            raise ValueError("shared local engine max_len mismatch")
        self.sync_rounds = 0
        self.reconstruction_mismatches = 0
        # continuous-batching state
        self._cache = None
        self._n_slots = 0
        self._prefill_len = 0

    # -- fabric synchronization --------------------------------------------

    def _step_kv_bytes(self, cache, prev_len) -> Dict[str, np.ndarray]:
        """Per-layer bytes of the K/V rows this decode step wrote: the
        cache row at each sequence's pre-step length (scalar or (B,)
        vector), K and V concatenated per layer. The rows are cut on
        the device and only they are copied to the host."""
        pl = np.asarray(prev_len)
        tracing.add(h2d_bytes=pl.nbytes)
        rows = np.asarray(_new_kv_rows(cache["k"], cache["v"], pl))
        tracing.add(d2h_bytes=rows.nbytes)
        return {f"kv{layer}": _bytes_of(rows[layer])
                for layer in range(rows.shape[0])}

    def _sync(self, logits, cache=None, prev_len=None):
        """One step's fabric synchronization point.

        Issues EVERY work of the step before waiting on any of them —
        the logits all-gather and one K/V-row all-gather per layer — so
        faults land mid-overlap. All of a step's works carry the
        ``latency_critical`` class: a decode step is a tail-latency SLO,
        so its chunks overtake queued bulk gradient buckets and
        background checkpoint streams at the per-(rank, peer) dispatch
        queues (DESIGN.md §10). It then waits the batch and
        byte-verifies each reconstruction against the local truth.
        Returns the logits rebuilt FROM FABRIC BYTES as a device array:
        the sampler only ever sees what the network delivered.
        """
        self.sync_rounds += 1
        if self.world is None:
            return logits
        with tracing.span("tp.logits_to_host"):
            lg = np.ascontiguousarray(np.asarray(logits))
            tracing.add(d2h_bytes=lg.nbytes)
        payloads = {"logits": _bytes_of(lg)}
        if cache is not None and prev_len is not None:
            with tracing.span("tp.kv_rows"):
                payloads.update(self._step_kv_bytes(cache, prev_len))
        works = {name: self.world.gather_replicated_async(
                     b, priority="latency_critical")
                 for name, b in payloads.items()}
        self.world.wait_all(list(works.values()), timeout=self.timeout)
        with tracing.span("tp.verify"):
            for name, b in payloads.items():
                for rec in works[name].result():
                    if not np.array_equal(rec, b):
                        self.reconstruction_mismatches += 1
        rec0 = works["logits"].result()[0]
        tracing.add(h2d_bytes=rec0.nbytes)
        return jnp.asarray(rec0.view(lg.dtype).reshape(lg.shape))

    # -- static batch generation -------------------------------------------

    def generate(self, prompts: np.ndarray, n_tokens: int,
                 greedy: bool = True, seed: int = 0,
                 prompt_lens: Optional[np.ndarray] = None) -> np.ndarray:
        """Tensor-parallel twin of :meth:`ServeEngine.generate`: same
        signature, same jitted compute, same sampling — plus a fabric
        synchronization every step. On a healthy (or SHIFT-masked)
        fabric the output is byte-identical to the single-host engine;
        corruption surfaces as wrong tokens because sampling consumes
        the reconstructed logits."""
        prompts = np.asarray(prompts)
        B, S = prompts.shape
        if S + n_tokens > self.max_len:
            raise ValueError(
                f"prompt ({S}) + generation ({n_tokens}) tokens exceed "
                f"max_len={self.max_len}")
        if prompt_lens is None:
            logits, cache = self._local._prefill_flat(self.params,
                                                      jnp.asarray(prompts))
        else:
            prompt_lens = np.asarray(prompt_lens, dtype=np.int32)
            if prompt_lens.shape != (B,):
                raise ValueError(f"prompt_lens shape {prompt_lens.shape} "
                                 f"!= ({B},)")
            if (prompt_lens < 1).any() or (prompt_lens > S).any():
                raise ValueError("prompt_lens must be in [1, S]")
            logits, cache = self._local._prefill(
                self.params, jnp.asarray(prompts),
                jnp.asarray(prompt_lens - 1))
        rec = self._sync(logits)
        out = [prompts]
        key = jax.random.PRNGKey(seed)
        for _ in range(n_tokens):
            nxt, key = self._local._sample(rec, greedy, key)
            out.append(np.asarray(nxt)[:, None])
            prev_len = np.asarray(cache["len"])
            logits, cache = self._local._decode(self.params, cache,
                                                nxt[:, None])
            rec = self._sync(logits, cache, prev_len)
        return np.concatenate(out, axis=1)

    # -- continuous batching -----------------------------------------------

    def start_batch(self, n_slots: int, prefill_len: int) -> None:
        """Allocate the persistent slot cache for continuous batching:
        ``n_slots`` concurrent sequences, per-sequence lengths, prompts
        admitted at a fixed ``prefill_len`` padding (one jit shape)."""
        if not 1 <= prefill_len <= self.max_len:
            raise ValueError("prefill_len must be in [1, max_len]")
        cache = self.model.init_cache(n_slots, self.max_len)
        cache["len"] = jnp.zeros((n_slots,), jnp.int32)
        self._cache = cache
        self._n_slots = n_slots
        self._prefill_len = prefill_len

    def admit(self, slot: int, prompt: np.ndarray) -> int:
        """Prefill ONE request and splice it into ``slot``: the prompt
        is right-padded to ``prefill_len``, prefilled alone (logits
        taken at its true last token — the ragged-prompt fix), its K/V
        rows and length written into the slot cache. Returns the
        request's first token, greedily sampled from the fabric-
        reconstructed prefill logits."""
        if self._cache is None:
            raise RuntimeError("start_batch() before admit()")
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        n = prompt.size
        if not 1 <= n <= self._prefill_len:
            raise ValueError(f"prompt length {n} outside "
                             f"[1, {self._prefill_len}]")
        with tracing.span("tp.admit"):
            padded = np.zeros((1, self._prefill_len), np.int32)
            padded[0, :n] = prompt
            last = np.asarray([n - 1], np.int32)
            tracing.add(h2d_bytes=padded.nbytes + last.nbytes)
            logits, pcache = self._local._prefill(
                self.params, jnp.asarray(padded), jnp.asarray(last))
            c = self._cache
            c["k"] = c["k"].at[:, slot].set(pcache["k"][:, 0])
            c["v"] = c["v"].at[:, slot].set(pcache["v"][:, 0])
            c["len"] = c["len"].at[slot].set(n)
            rec = self._sync(logits)
            tok = np.asarray(jnp.argmax(rec[:, -1], axis=-1))
            tracing.add(d2h_bytes=tok.nbytes)
            return int(tok[0])

    def decode_batch(self, feed: np.ndarray) -> np.ndarray:
        """One decode step over the whole slot batch. ``feed`` is the
        (n_slots,) token vector (free slots carry don't-care tokens —
        their rows compute deterministic garbage that is never read).
        Returns the (n_slots,) greedy next tokens sampled from the
        fabric-reconstructed logits."""
        if self._cache is None:
            raise RuntimeError("start_batch() before decode_batch()")
        feed = np.asarray(feed, dtype=np.int32).reshape(-1)
        if feed.size != self._n_slots:
            raise ValueError(f"feed size {feed.size} != {self._n_slots}")
        with tracing.span("tp.decode"):
            prev_len = np.asarray(self._cache["len"])
            tracing.add(d2h_bytes=prev_len.nbytes, h2d_bytes=feed.nbytes)
            logits, self._cache = self._local._decode(
                self.params, self._cache, jnp.asarray(feed)[:, None])
        rec = self._sync(logits, self._cache, prev_len)
        if "expert_rows" in self._cache:
            self._expert_counts()
        toks = np.asarray(jnp.argmax(rec[:, -1], axis=-1))
        tracing.add(d2h_bytes=toks.nbytes)
        return toks.astype(np.int32)

    def _expert_counts(self) -> None:
        """The rows the last decode step routed to each held expert of
        each MoE layer (free slots' rows too), read once its logits have
        reached the host: a few bytes and no wait for the device."""
        with tracing.span("tp.expert_counts"):
            rows = np.asarray(self._cache["expert_rows"])
            tracing.add(d2h_bytes=rows.nbytes,
                        experts_hit=int(np.count_nonzero(rows)),
                        experts_run=int(rows.size),
                        expert_rows=int(rows.sum()))
