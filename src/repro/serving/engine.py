"""Batched serving engine on the Harmonia stack.

Request flow:
  1. requests are left-padded to a common aligned length (the packed
     asymmetric cache shares one position counter; per-row validity is a
     ``pad_prefix`` mask),
  2. ``prefill``: INT4 weights x BFP activations, builds the packed
     asymmetric KV cache (init/bulk/local regions) + online K offsets,
  3. ``decode``: by default the *fused on-device loop* — one jitted
     ``lax.scan`` (``lm.generate_loop``) that embeds, decode-steps,
     samples and appends per iteration, with the cache donated
     (``donate_argnums``) so predicated writes mutate it in place.  The
     legacy one-dispatch-per-token host loop is kept behind
     ``fused=False`` for regression and benchmarking.

``ServeLoop`` implements continuous batching on top of the fused loop's
``max_steps``-chunked continuation form: finished rows are re-prefilled
with queued requests into the freed cache rows at chunk boundaries (the
shared position counter stays GROUP-aligned because chunks are ALIGN
multiples).

Mesh-sharded serving (``EngineConfig.mesh``): when a ``jax.sharding.Mesh``
is configured, params are placed per ``distributed.sharding.param_pspecs``
(Megatron column/row tensor parallelism on the ``model`` axis — GSPMD
inserts the single all-reduce per O/down projection), the packed KV cache
per ``cache_pspecs`` (batch rows on the data axis, kv-heads — or head_dim
for non-divisible GQA — on ``model``) and the prompt batch per
``batch_pspec``.  Prefill, the per-token decode step, the fused loops and
the continuous-batching row swap are jitted with explicit
``in_shardings``/``out_shardings`` plus cache donation, so the cache is
born sharded at prefill and stays sharded and in place across every decode
step and row swap — it is never gathered to a replicated copy.

Throughput accounting reports raw tokens/s (every decoded position),
``useful_tokens_per_s`` (EOS-truncated) and the modeled HBM traffic saved
by the 4-bit bulk cache (fp16 baseline vs packed actual).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import kvcache
from repro.core.quant_config import QuantConfig, harmonia
from repro.data.tokenizer import ByteTokenizer
from repro.models import lm
from repro.models.config import ModelConfig
from repro.serving import sampler as sampler_lib

ALIGN = 32  # prefill lengths must be multiples of the BFP group


def ceil_align(n: int) -> int:
    """Round up to the next ALIGN multiple — the shared-counter alignment
    invariant every prefill length and chunk boundary must satisfy."""
    return -(-n // ALIGN) * ALIGN


@dataclasses.dataclass
class EngineConfig:
    max_seq: int = 512
    max_new_tokens: int = 64
    quant: Optional[QuantConfig] = None      # defaults to harmonia(4)
    sampler: str = "greedy"
    temperature: float = 0.8
    seed: int = 0
    # Route the serving hot paths through the grid-fused Pallas kernels:
    # prefill attention consumes K/V packed by the in-kernel FP->BFP
    # converters, the packed cache is built by the single-launch
    # converter (only packed bytes hit HBM), and each decode step reads
    # all three asymmetric-cache regions through one single-launch
    # kernel (bulk tiles + in-kernel init/local epilogue and flash
    # merge).  Off by default: the XLA path keeps the fake-quant P
    # numerics used by the accuracy benchmarks.
    use_pallas_kernels: bool = False
    # Run generation through the fused on-device loop (single dispatch
    # for the whole decode, donated in-place cache).  ``False`` restores
    # the per-token host loop (kept for regression/benchmarks).
    fused_loop: bool = True
    # Optional jax.sharding.Mesh with ("data", "model") (+"pod") axes:
    # mesh-sharded tensor-parallel serving (see module docstring).  None
    # keeps the single-device path byte-for-byte unchanged.
    mesh: Optional[Any] = None


def scatter_rows(dst, src, rows: Sequence[int], batch: int):
    """Scatter the rows of cache-tree ``src`` (batch ``len(rows)``) into
    rows ``rows`` of ``dst`` (batch ``batch``).

    Cache leaves carry the batch axis at different positions (axis 0 for
    remainder-block caches, axis 2 for scan-stacked ``(n_rep, c_k, B,
    ...)`` leaves), so the axis is located per leaf as the unique axis
    where the shapes differ by exactly ``batch`` vs ``len(rows)``.
    Leaves with identical shapes are row-independent (position counters,
    ring slot positions) and must already agree — the serving loop only
    swaps rows at matching shared-counter values — so ``dst``'s copy is
    kept.
    """
    n = len(rows)
    if n == batch:
        raise ValueError("full-batch scatter: replace the cache instead")
    rows_arr = jnp.asarray(rows)     # list of ints or a (traced) array

    def leaf(d, s):
        if d.shape == s.shape:
            return d
        for ax in range(d.ndim):
            if (d.shape[ax] == batch and s.shape[ax] == n
                    and d.shape[:ax] == s.shape[:ax]
                    and d.shape[ax + 1:] == s.shape[ax + 1:]):
                idx = (slice(None),) * ax + (rows_arr,)
                return d.at[idx].set(s.astype(d.dtype))
        raise ValueError(f"no batch axis found: dst {d.shape} src {s.shape}")

    return jax.tree.map(leaf, dst, src)


class Engine:
    def __init__(self, params, cfg: ModelConfig, ecfg: EngineConfig):
        self.cfg = cfg
        self.ecfg = ecfg
        self.quant = ecfg.quant or harmonia(4)
        self.tok = ByteTokenizer()
        self.mesh = ecfg.mesh
        self._param_sh = None
        self._cache_sh: Dict[int, Any] = {}   # batch -> NamedSharding tree
        self._mesh_jits: Dict = {}
        if self.mesh is not None:
            from repro.distributed import sharding as dshard
            self._dshard = dshard
            self._param_sh = dshard.to_named(
                dshard.param_pspecs(cfg, params, self.mesh), self.mesh)
            params = jax.device_put(params, self._param_sh)
        self.params = params
        self._prefill = jax.jit(
            lambda p, t: lm.prefill(p, cfg, t, max_seq=ecfg.max_seq,
                                    quant=self.quant,
                                    use_pallas=ecfg.use_pallas_kernels))

        def serve_step(p, t, c, pp):
            """One decode step of the host loop."""
            return lm.decode_step(p, cfg, t, c, quant=self.quant,
                                  pad_prefix=pp,
                                  use_pallas=ecfg.use_pallas_kernels)

        # donate the cache: append_token's predicated writes let XLA alias
        # every region buffer in place instead of allocating a second cache
        self._serve_step = serve_step
        self._decode = jax.jit(serve_step, donate_argnums=2)
        self._sample: Callable = sampler_lib.make_sampler(
            ecfg.sampler, temperature_value=ecfg.temperature)
        if self.mesh is not None:
            # Fence the sampler into a replicated subgraph: constrain its
            # logits input AND its token output (works both eagerly and
            # inside the fused loop's trace).  Without both fences GSPMD
            # propagates the batch sharding of neighbouring ops into the
            # sampler's threefry computation, and the non-partitionable
            # RNG draws *different bits* when partitioned — sampled
            # tokens silently diverge from the unsharded engine even
            # though the logits agree (observed: a batch-sharded
            # categorical flips tokens with top-2 gaps of O(1)).  The
            # all-gather this inserts is one (B, V) fp32 per step —
            # noise next to a decode step.
            raw_sample, rep = self._sample, self._rep_sh()

            def _sample_replicated(lg, k):
                tok = raw_sample(
                    jax.lax.with_sharding_constraint(lg, rep), k)
                return jax.lax.with_sharding_constraint(tok, rep)
            self._sample = _sample_replicated
        self._loops: Dict = {}

    # -- mesh-sharded jit builders ---------------------------------------
    # Small per-row arrays (token, pad_prefix, finished) deliberately get
    # no pinned in_shardings: the ServeLoop mutates them eagerly between
    # chunks (``.at[rows].set``), and a pinned spec would reject the
    # committed result — GSPMD infers their layout from the batch-sharded
    # logits instead.  Params and caches, the two large operands, are
    # always pinned; every cache producer also pins out_shardings, so the
    # cache's sharding is invariant along prefill -> loop -> swap chains
    # and donation aliases shard buffers in place.

    def _named(self, spec):
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh, spec)

    def _batch_sh(self, B: int):
        return self._named(self._dshard.batch_pspec(self.mesh, B))

    def _rep_sh(self):
        from jax.sharding import PartitionSpec as P
        return self._named(P())

    def cache_shardings(self, B: int):
        """NamedSharding tree for the batch-``B`` serving cache (memoized;
        cache shapes depend only on batch and ``max_seq``)."""
        if B not in self._cache_sh:
            toks = jax.ShapeDtypeStruct((B, ALIGN), jnp.int32)
            _, acaches = jax.eval_shape(
                lambda p, t: lm.prefill(p, self.cfg, t,
                                        max_seq=self.ecfg.max_seq,
                                        quant=self.quant),
                self.params, toks)
            specs = self._dshard.cache_pspecs(acaches, self.mesh, B)
            self._cache_sh[B] = self._dshard.to_named(specs, self.mesh)
        return self._cache_sh[B]

    def prefill(self, toks):
        """Prefill dispatch: the plain jit, or the mesh-sharded jit whose
        out_shardings make the cache *born* sharded."""
        if self.mesh is None:
            return self._prefill(self.params, toks)
        B, S = toks.shape
        key = ("prefill", B, S)
        if key not in self._mesh_jits:
            self._mesh_jits[key] = jax.jit(
                lambda p, t: lm.prefill(p, self.cfg, t,
                                        max_seq=self.ecfg.max_seq,
                                        quant=self.quant,
                                        use_pallas=self.ecfg.use_pallas_kernels),
                in_shardings=(self._param_sh, self._batch_sh(B)),
                out_shardings=(self._batch_sh(B), self.cache_shardings(B)))
        return self._mesh_jits[key](self.params, toks)

    def decode(self, tok, caches, pad_prefix):
        """One decode step (host-loop path) under the active placement."""
        if self.mesh is None:
            return self._decode(self.params, tok, caches, pad_prefix)
        B = int(tok.shape[0])
        key = ("decode", B)
        if key not in self._mesh_jits:
            c_sh = self.cache_shardings(B)
            self._mesh_jits[key] = jax.jit(
                self._serve_step,
                in_shardings=(self._param_sh, None, c_sh, None),
                out_shardings=(self._batch_sh(B), c_sh),
                donate_argnums=2)
        return self._mesh_jits[key](self.params, tok, caches, pad_prefix)

    def scatter_cache_rows(self, dst, src, rows: Sequence[int], batch: int):
        """Sharding-preserving continuous-batching row swap.  Under a mesh
        the per-row updates run as a jitted scatter with both cache trees'
        shardings pinned and the destination donated — the sharded cache
        is patched on-device, never gathered to host or to a replicated
        copy."""
        if self.mesh is None:
            return scatter_rows(dst, src, rows, batch)
        key = ("scatter", batch, len(rows))
        if key not in self._mesh_jits:
            c_sh = self.cache_shardings(batch)

            def serve_swap_rows(d, s, r):
                return scatter_rows(d, s, r, batch)
            self._mesh_jits[key] = jax.jit(
                serve_swap_rows,
                in_shardings=(c_sh, self.cache_shardings(len(rows)), None),
                out_shardings=c_sh, donate_argnums=0)
        return self._mesh_jits[key](dst, src, jnp.asarray(list(rows)))

    def _fused(self, num_steps: int, start: bool,
               batch: Optional[int] = None):
        """Memoized jitted fused loop (cache donated).

        ``start=True``: takes prefill logits, emits ``num_steps`` tokens
        (first sampled from the logits).  ``start=False``: continuation —
        takes the last emitted token + finished mask, emits ``num_steps``
        decode tokens (the ServeLoop chunk primitive).  ``batch`` is
        required under a mesh (shardings are built per batch size).
        """
        memo_key = (num_steps, start, batch if self.mesh is not None
                    else None)
        if memo_key not in self._loops:
            common = dict(num_steps=num_steps, sample_fn=self._sample,
                          eos_id=self.tok.eos_id, quant=self.quant,
                          use_pallas=self.ecfg.use_pallas_kernels)
            jit_kw: Dict = {}
            if self.mesh is not None:
                if batch is None:
                    raise ValueError("mesh-sharded fused loop needs the "
                                     "batch size")
                c_sh = self.cache_shardings(batch)
                b_sh = self._batch_sh(batch)
                common["cache_shardings"] = c_sh
                out_sh = {"tokens": b_sh, "caches": c_sh, "finished": b_sh,
                          "last_tok": b_sh, "key": self._rep_sh()}
                n_in = 5 if start else 6
                jit_kw = dict(
                    in_shardings=(self._param_sh, None, c_sh)
                    + (None,) * (n_in - 3),
                    out_shardings=out_sh)
            if start:
                def f(p, logits0, caches, pp, key):
                    return lm.generate_loop(p, self.cfg, caches,
                                            logits0=logits0, key=key,
                                            pad_prefix=pp, **common)
            else:
                def f(p, tok, caches, pp, key, finished):
                    return lm.generate_loop(p, self.cfg, caches,
                                            tok0=tok, key=key,
                                            finished=finished,
                                            pad_prefix=pp, **common)
            self._loops[memo_key] = jax.jit(f, donate_argnums=2, **jit_kw)
        return self._loops[memo_key]

    # -- batching --
    def _encode(self, prompt: str) -> List[int]:
        """Token ids of ``prompt``, truncated to leave one ALIGN block of
        the cache for generation."""
        return self.tok.encode(prompt)[: self.ecfg.max_seq - ALIGN]

    def _prepare(self, prompts: List[str]):
        """Encode, truncate, vocab-clip and left-pad to a shared
        ALIGN-multiple length."""
        if not prompts:
            raise ValueError("prompts must be a non-empty list")
        return self._pad_batch([self._encode(p) for p in prompts])

    def _pad_batch(self, ids: List[List[int]],
                   padded_len: Optional[int] = None):
        """Left-pad ``ids`` to ``padded_len`` (the serving loop's row
        re-prefill at the shared position counter), by default to the
        longest one's ALIGN multiple: (tokens (B, S), pad_prefix (B,))."""
        if padded_len is None:
            # all-empty prompt lists would otherwise yield padded_len == 0
            # and degenerate (B, 0) model shapes — always allocate one
            # ALIGN block
            padded_len = max(ALIGN, ceil_align(max(len(x) for x in ids)))
        B = len(ids)
        toks = np.full((B, padded_len), self.tok.pad_id, np.int32)
        pad_prefix = np.zeros((B,), np.int32)
        for i, x in enumerate(ids):
            if x:
                toks[i, padded_len - len(x):] = x     # left pad
            pad_prefix[i] = padded_len - len(x)
        toks = np.minimum(toks, self.cfg.vocab_size - 1)
        return jnp.asarray(toks), jnp.asarray(pad_prefix)

    def generate(self, prompts: List[str],
                 max_new_tokens: Optional[int] = None,
                 fused: Optional[bool] = None) -> dict:
        """Returns {texts, tokens, tokens_per_s, useful_tokens_per_s,
        cache_stats}.  ``fused=None`` follows ``ecfg.fused_loop``."""
        m = max_new_tokens or self.ecfg.max_new_tokens
        fused = self.ecfg.fused_loop if fused is None else fused
        if not prompts:
            return {"texts": [], "tokens": np.zeros((0, m), np.int32),
                    "tokens_per_s": 0.0, "useful_tokens_per_s": 0.0,
                    "wall_s": 0.0, "cache_stats": {}}
        toks, pad_prefix = self._prepare(prompts)
        B, S = toks.shape
        if S + m - 1 > self.ecfg.max_seq:
            # emitting m tokens appends only m-1 (the first is sampled
            # from prefill logits, the last is never appended); past
            # capacity the K ring would wrap over live tokens and bulk
            # writes clip onto the last slot — refuse loudly instead of
            # silently corrupting the packed cache
            raise ValueError(
                f"prompt length {S} + max_new_tokens {m} - 1 exceeds "
                f"max_seq {self.ecfg.max_seq}")
        key = jax.random.PRNGKey(self.ecfg.seed)

        t0 = time.time()
        logits, caches = self.prefill(toks)
        if fused:
            out = self._fused(m, start=True, batch=B)(
                self.params, logits, caches, pad_prefix, key)
            gen = out["tokens"]
            caches = out["caches"]
        else:
            out_list = []
            tok = self._sample(logits, key)
            out_list.append(tok)
            for _ in range(m - 1):
                key, sk = jax.random.split(key)
                logits, caches = self.decode(tok, caches, pad_prefix)
                tok = self._sample(logits, sk)
                out_list.append(tok)
            gen = jnp.stack(out_list, axis=1)
        jax.block_until_ready(gen)
        dt = time.time() - t0

        texts = []
        useful = 0
        arr = np.asarray(gen)
        for i in range(B):
            row = arr[i]
            stop = np.where(row == self.tok.eos_id)[0]
            row = row[: stop[0]] if len(stop) else row
            useful += len(row)
            texts.append(self.tok.decode(row.tolist()))

        stats = self._cache_stats(caches, S + m)
        return {"texts": texts, "tokens": arr,
                "tokens_per_s": B * m / dt,
                "useful_tokens_per_s": useful / dt,
                "wall_s": dt, "cache_stats": stats}

    def _cache_stats(self, caches, seq_len: int) -> dict:
        packed = 0
        for leaf in jax.tree.leaves(caches):
            if hasattr(leaf, "dtype"):
                packed += leaf.size * leaf.dtype.itemsize
        n_attn = sum(n for k, n in self.cfg.kind_counts().items()
                     if k in ("attn", "local_attn"))
        fp16 = (n_attn * kvcache.fp16_cache_bytes(
            1, self.cfg.n_kv_heads, self.cfg.head_dim, self.ecfg.max_seq))
        return {"packed_cache_bytes_total": int(packed),
                "fp16_equiv_per_row": int(fp16),
                "storage_fraction":
                    self.quant.kv.storage_fraction(seq_len)}


@dataclasses.dataclass
class RequestRecord:
    """One request's way through ``ServeLoop.serve``.  Times are
    ``time.perf_counter()`` seconds."""
    admitted: float                     # taken into a batch row
    first_token: Optional[float] = None  # its first token on the host
    finished: Optional[float] = None    # finalized
    tokens: Optional[List[int]] = None  # served ids, cut at budget and EOS


SERVE_STATS = ("waves", "chunks", "swaps", "prefills", "prefill_tokens",
               "prefill_padded_tokens", "decode_steps", "decode_row_steps")


class ServeLoop:
    """Continuous batching over the fused loop's chunked continuation.

    A fixed-width batch decodes in ``max_steps``-sized on-device chunks;
    at chunk boundaries, rows that finished (EOS or budget) are
    re-prefilled with queued requests into the freed cache rows
    (``scatter_rows``), so the batch never drains to serve the queue.
    ``max_steps`` is rounded up to an ALIGN multiple: the packed cache
    shares one position counter across rows, and keeping chunk boundaries
    GROUP-aligned is what lets a fresh request prefill to exactly the
    current counter value.  When every row has drained and requests
    remain, a fresh wave restarts the counter instead (cheaper than
    re-prefilling at a long padded length).

    What a ``serve`` call did, reset by the next one:

    * ``stats``: ``waves``; ``chunks``; ``swaps`` (rows re-prefilled);
      ``prefills`` (dispatches, swap-ins included); ``prefill_tokens``
      (real prompt tokens) and ``prefill_padded_tokens`` (B x S
      dispatched); ``decode_steps`` and ``decode_row_steps`` (steps x
      batch rows);
    * ``records``: a ``RequestRecord`` per request, in request order;
    * host spans in the profiler's trace (``jax.profiler.
      TraceAnnotation``, inert unless a trace is being taken):
      ``serve.wave`` around each wave, and in it ``serve.prepare``
      (encode and pad), ``serve.prefill`` (dispatch; carries its real
      ``tokens`` and the ``padded`` B x S) and ``serve.chunk`` (dispatch;
      carries its counter ``pos``, ``steps`` and ``rows``),
      ``serve.swap_in``, ``serve.wait`` (the host blocked on a device
      result) and ``serve.finalize``.
    """

    def __init__(self, engine: Engine, batch_size: int = 4,
                 max_steps: int = ALIGN):
        self.engine = engine
        self.batch = batch_size
        self.max_steps = max(ALIGN, ceil_align(max_steps))
        self.stats = dict.fromkeys(SERVE_STATS, 0)
        self.records: List[Optional[RequestRecord]] = []

    def serve(self, prompts: List[str],
              max_new_tokens: Union[int, Sequence[int], None] = None
              ) -> List[str]:
        if not prompts:
            return []
        if isinstance(max_new_tokens, (list, tuple)):
            if len(max_new_tokens) != len(prompts):
                raise ValueError("per-request budgets must match prompts")
            budgets = list(max_new_tokens)
        else:
            budgets = [max_new_tokens
                       or self.engine.ecfg.max_new_tokens] * len(prompts)
        results: List[Optional[str]] = [None] * len(prompts)
        queue = list(range(len(prompts)))
        self.stats = dict.fromkeys(SERVE_STATS, 0)
        self.records = [None] * len(prompts)
        while queue:
            with TraceAnnotation("serve.wave"):
                queue = self._run_wave(prompts, budgets, queue, results)
        return results

    # -- one wave: a batch of rows decoded to completion, with row swaps --
    def _finalize(self, req: int, toks: List[int], budget: int,
                  results: List[Optional[str]]):
        seq = toks[:budget]
        eos = self.engine.tok.eos_id
        if eos in seq:
            seq = seq[: seq.index(eos)]
        results[req] = self.engine.tok.decode(seq)
        rec = self.records[req]
        rec.finished, rec.tokens = time.perf_counter(), seq

    def _admit(self, reqs: List[int]):
        now = time.perf_counter()
        for i in reqs:
            self.records[i] = RequestRecord(admitted=now)

    def _prefill(self, ids: List[List[int]], toks):
        """Dispatch the prefill of ``toks``, the padded ``ids``."""
        B, S = toks.shape
        real = sum(len(x) for x in ids)
        self.stats["prefills"] += 1
        self.stats["prefill_tokens"] += real
        self.stats["prefill_padded_tokens"] += B * S
        with TraceAnnotation("serve.prefill", tokens=real, padded=B * S):
            return self.engine.prefill(toks)

    def _first_tokens(self, reqs: List[int], tok) -> np.ndarray:
        """``tok`` on the host, the first token of each of ``reqs``."""
        with TraceAnnotation("serve.wait"):
            host = np.asarray(tok)
        now = time.perf_counter()
        for i in reqs:
            self.records[i].first_token = now
        return host

    def _run_wave(self, prompts, budgets, queue, results):
        eng = self.engine
        self.stats["waves"] += 1
        B = min(self.batch, len(queue))
        wave, queue = queue[:B], queue[B:]
        with TraceAnnotation("serve.prepare"):
            ids = [eng._encode(prompts[i]) for i in wave]
            toks, pad_prefix = eng._pad_batch(ids)
        self._admit(wave)
        key = jax.random.PRNGKey(eng.ecfg.seed)
        logits, caches = self._prefill(ids, toks)
        tok = eng._sample(logits, key)          # first token of every row
        eos = eng.tok.eos_id
        finished = tok == eos
        row_req: List[Optional[int]] = list(wave)
        first = self._first_tokens(wave, tok)
        row_toks: List[List[int]] = [[int(first[r])] for r in range(B)]

        while True:
            # finalize satisfied rows (EOS or budget reached) — checked
            # before every chunk, so a budget of 1 / an EOS first token
            # never costs a full decode chunk
            with TraceAnnotation("serve.finalize"):
                for r in range(B):
                    if row_req[r] is None:
                        continue
                    budget = budgets[row_req[r]]
                    ts = row_toks[r]
                    if eos in ts[:budget] or len(ts) >= budget:
                        self._finalize(row_req[r], ts, budget, results)
                        row_req[r] = None
            live = [r for r in range(B) if row_req[r] is not None]
            if not live:
                break                            # fresh wave is cheaper
            free = [r for r in range(B) if row_req[r] is None]
            with TraceAnnotation("serve.wait"):
                cur = int(caches["_pos"])
            if free and queue and cur < eng.ecfg.max_seq:
                with TraceAnnotation("serve.swap_in"):
                    caches, pad_prefix, tok, finished, queue = \
                        self._swap_in(prompts, budgets, queue, free, cur,
                                      caches, pad_prefix, tok, finished,
                                      row_req, row_toks)
                live = [r for r in range(B) if row_req[r] is not None]
            # rows that stayed free (empty queue / no room): freeze
            idle = [r for r in range(B) if row_req[r] is None]
            if idle:
                finished = finished.at[jnp.asarray(idle)].set(True)
            # chunk length: capacity- and budget-capped, kept an ALIGN
            # multiple so the shared counter stays aligned for swap-ins
            max_rem = max(budgets[row_req[r]] - len(row_toks[r])
                          for r in live)
            steps = min(self.max_steps, eng.ecfg.max_seq - cur,
                        ceil_align(max_rem))
            if steps <= 0:
                break                            # cache capacity reached
            with TraceAnnotation("serve.chunk", pos=cur, steps=steps,
                                 rows=B):
                out = eng._fused(steps, start=False, batch=B)(
                    eng.params, tok, caches, pad_prefix, key, finished)
            caches, key = out["caches"], out["key"]
            finished, tok = out["finished"], out["last_tok"]
            self.stats["chunks"] += 1
            self.stats["decode_steps"] += steps
            self.stats["decode_row_steps"] += steps * B
            with TraceAnnotation("serve.wait"):
                chunk = np.asarray(out["tokens"])
            for r in live:
                row_toks[r].extend(chunk[r].tolist())
        with TraceAnnotation("serve.finalize"):
            for r in range(B):
                if row_req[r] is not None:       # capacity-truncated rows
                    self._finalize(row_req[r], row_toks[r],
                                   budgets[row_req[r]], results)
        return queue

    def _swap_in(self, prompts, budgets, queue, free, cur, caches,
                 pad_prefix, tok, finished, row_req, row_toks):
        """Re-prefill queued requests into freed rows at counter ``cur``.

        FIFO: stops at the first queued request this wave cannot serve as
        well as a fresh wave would — the prompt must fit into ``cur``
        positions, and the remaining cache capacity must cover the
        request's budget (or as much of it as a fresh wave could), so a
        late swap-in is never capacity-truncated below what it would get
        by waiting.
        """
        eng = self.engine
        max_seq = eng.ecfg.max_seq
        rows: List[int] = []
        new_reqs: List[int] = []
        new_ids: List[List[int]] = []
        for r in free:
            if not queue:
                break
            ids = eng._encode(prompts[queue[0]])
            fresh_len = max(ALIGN, ceil_align(len(ids)))
            fresh_cap = 1 + max_seq - fresh_len    # tok0 + decode room
            need = min(budgets[queue[0]], fresh_cap)
            if len(ids) > cur or 1 + max_seq - cur < need:
                break
            rows.append(r)
            new_reqs.append(queue.pop(0))
            new_ids.append(ids)
        if not rows:
            return caches, pad_prefix, tok, finished, queue
        sub, sub_pp = eng._pad_batch(new_ids, cur)
        self._admit(new_reqs)
        lg_n, c_n = self._prefill(new_ids, sub)
        tok_n = eng._sample(lg_n, jax.random.PRNGKey(
            eng.ecfg.seed + 1 + new_reqs[0]))
        B = int(tok.shape[0])
        caches = eng.scatter_cache_rows(caches, c_n, rows, B)
        rows_arr = jnp.asarray(rows)
        pad_prefix = pad_prefix.at[rows_arr].set(sub_pp)
        tok = tok.at[rows_arr].set(tok_n)
        finished = finished.at[rows_arr].set(tok_n == eng.tok.eos_id)
        arr_n = self._first_tokens(new_reqs, tok_n)
        for j, r in enumerate(rows):
            row_req[r] = new_reqs[j]
            row_toks[r] = [int(arr_n[j])]
        self.stats["swaps"] += len(rows)
        return caches, pad_prefix, tok, finished, queue


__all__ = ["Engine", "EngineConfig", "ServeLoop", "RequestRecord",
           "SERVE_STATS", "scatter_rows", "ALIGN", "ceil_align"]
