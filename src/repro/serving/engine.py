"""Continuous-batching serving engine fused with distributed feature joins.

The paper's thesis is deep learning and data engineering composed in one
efficient program (the UNOMT end-to-end application, Fig. 11).  This
module turns the repo's two halves — ``launch/serve.py``'s batched
prefill/decode with a static KV cache, and ``core/dist_ops.py``'s
distributed table operators — into one continuously *serving* system:

admission -> feature fetch -> slot prefill -> continuous-batching decode

* **Admission** (:class:`~repro.serving.queue.AdmissionQueue`): bounded,
  rejections counted — backpressure, never silent drops (the same
  counted-overflow contract as the table kernels).
* **Feature fetch** (:class:`FeatureStore`): each request's drug/RNA keys
  resolve against device-resident feature tables through the engine's own
  distributed operators — the resident side is hash-shuffled once at
  ingest (streamed from host in morsels, ``np.memmap``-backed sources
  included, so the feature tables may exceed device memory), and every
  micro-batch of keys runs shuffle + local join through one cached
  :class:`~repro.core.dist_ops.DistributedPipeline` — i.e. ``dist_join``
  with the build-side shuffle hoisted out of the request path.
* **Slot prefill** (``models.model.make_slot_prefill``): prompts are
  right-padded to one fixed shape, so every request — any prompt length —
  re-enters a single jitted prefill; the resulting KV cache is scattered
  into the running batch cache at the freed slot
  (``models.model.write_cache_slot``).
* **Decode** (``models.model.make_serve_step`` with per-slot cache
  lengths): ONE cached jitted ``serve_step`` with donated cache buffers
  drives the whole fixed-shape batch; as sequences finish, their slots
  are refilled from the queue, one request a step (continuous batching —
  the batch never drains to a barrier, and no token waits behind more
  than one prefill).

Every stage reports into :class:`~repro.serving.metrics.ServingMetrics`
(queue depth, rejects, slot occupancy, tokens/s inputs, latency series).
One code path serves dense, Mamba-1, Mamba-2 and MoE configs: each slot's
cache holds what its layers need (KV, or conv and SSM state taken at the
prompt's true length), and the MoE layers' dropped pairs are counted
(``moe_pairs_dropped``); on one device, each decode step's pairs per
held expert go on its ``serve/decode`` span as ``expert_pairs``.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import trace
from ..core import dist_ops as D
from ..core import local_ops as L
from ..core import morsel as Mo
from ..core.context import HptmtContext
from ..core.table import narrow_column
from ..models import model as M
from .batcher import SlotBatch
from .metrics import ServingMetrics
from .queue import AdmissionQueue

__all__ = ["Request", "FeatureStore", "ServingEngine"]


# --------------------------------------------------------------------------
# Requests
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    """One serving request: a prompt to decode plus feature-table keys.

    ``status`` walks ``queued -> active -> done`` (or ``rejected`` at the
    admission queue / ``feature_miss`` when a key has no feature row —
    both *counted* terminals, never silent)."""

    req_id: int
    prompt: np.ndarray                      # (L,) int32 token ids
    gen_len: int                            # tokens to generate (>= 1)
    drug_id: int | None = None
    cell_id: int | None = None
    status: str = "new"
    features: dict[str, float] | None = None
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0


# --------------------------------------------------------------------------
# Feature store: resident distributed feature table + cached lookup
# --------------------------------------------------------------------------


def _dropped(d) -> int:
    a = np.asarray(d)
    return int(a.max()) if a.size else 0


class FeatureStore:
    """Device-resident distributed feature table with a cached lookup path.

    ``source`` (a column mapping or a :class:`~repro.core.morsel
    .ChunkedTable` — ``np.memmap`` columns stream without copies, so the
    table may exceed device memory) is ingested once: each host morsel is
    hash-shuffled on ``key_col`` and appended into a per-shard resident
    accumulator (``local_ops.append_rows``, buffers donated through the
    chunk loop).  Keys must be unique (run ``dist_unique`` upstream —
    UNOMT Fig. 10 — for sources with duplicates).

    ``lookup(keys)`` then resolves a micro-batch of keys with the same
    decomposition as ``dist_ops.dist_join``: shuffle the probe on the key
    (equal keys co-locate with the resident rows), local inner join per
    shard, collect — but the build-side shuffle is *hoisted out of the
    request path* (done once at ingest), and the probe pipeline is one
    cached :class:`~repro.core.dist_ops.DistributedPipeline` whose static
    probe capacity admits any batch up to ``probe_capacity`` without
    retracing.  ``contains(keys)`` is the matching membership path
    (``dist_isin``'s shuffle + local ``isin``, same hoisting).

    Shuffle/join slabs are sized *skew-proof* for the probe: every key in
    a micro-batch may hash to one shard (hot-key traffic), so
    ``slots_per_dest`` covers a full sender and the receive/output
    capacity covers the whole world's probe rows — lookups never drop.
    Any residual overflow (ingest imbalance past ``overcommit``) is
    counted in ``self.dropped``, never silent.
    """

    def __init__(self, ctx: HptmtContext, key_col: str, source, *,
                 probe_capacity: int, chunk_rows: int | None = None,
                 overcommit: float = 2.0,
                 resident_capacity_per_shard: int | None = None):
        if probe_capacity <= 0:
            raise ValueError("probe_capacity must be positive")
        self.ctx = ctx
        self.key_col = key_col
        self.probe_capacity = int(probe_capacity)
        self.dropped = 0
        world = ctx.world_size

        if isinstance(source, Mo.ChunkedTable):
            src = source
        else:
            cols = {k: np.asarray(v) for k, v in source.items()}
            n = len(next(iter(cols.values())))
            src = Mo.ChunkedTable(cols, chunk_rows or max(n, 1))
        if key_col not in src.names:
            raise ValueError(f"key column {key_col!r} not in source "
                             f"columns {src.names}")
        self.n_rows = src.nrows
        self.feature_cols = tuple(k for k in src.names if k != key_col)

        rcap = resident_capacity_per_shard or max(
            1, math.ceil(src.nrows / world * overcommit))
        acc = D.distribute_table(
            ctx, {k: narrow_column(k, v[:0]) for k, v in
                  src.columns.items()},
            capacity_per_shard=rcap)

        def ingest_step(c, a, chunk):
            # skew-proof slab: a whole morsel may hash to one shard, so a
            # sender may route every row to one dest and a receiver may
            # take the full chunk — ingest itself never drops (only the
            # resident append can overflow, counted, past `overcommit`)
            per = chunk.capacity
            sh, d = D.shuffle(c, chunk, [key_col], slots_per_dest=per,
                              out_capacity=c.world_size * per)
            a2, ad = L.append_rows(a, sh)
            return a2, d + jax.lax.psum(ad, c.row_axes)

        ingest = D.DistributedPipeline(ctx, ingest_step,
                                       donate_argnums=(0,))
        for g in src.distribute(ctx):
            acc, d = ingest(acc, g)
            self.dropped += _dropped(d)
        self.resident = acc

        # probe sizing: a micro-batch of `probe_capacity` keys, every one
        # of which may route to a single shard (skewed/hot keys)
        pcap = max(1, math.ceil(self.probe_capacity / world))
        self._probe_cap_per_shard = pcap
        out_cap = world * pcap

        def lookup_step(c, build, probe):
            sh, d = D.shuffle(c, probe, [key_col], slots_per_dest=pcap,
                              out_capacity=out_cap)
            out, jd = L.join(sh, build, left_on=[key_col], how="inner",
                             out_capacity=out_cap, return_overflow=True)
            return out, d + jax.lax.psum(jd, c.row_axes)

        def contains_step(c, build, probe):
            sh, d = D.shuffle(c, probe, [key_col], slots_per_dest=pcap,
                              out_capacity=out_cap)
            mask, over = L.isin(sh, key_col, build, key_col,
                                return_overflow=True)
            return L.select(sh, mask), \
                d + jax.lax.psum(over, c.row_axes)

        self._lookup = D.DistributedPipeline(ctx, lookup_step)
        self._contains = D.DistributedPipeline(ctx, contains_step)

    # ---------------------------------------------------------------- probes
    def _distribute_probe(self, keys: np.ndarray):
        keys = np.asarray(keys)
        if keys.ndim != 1:
            raise ValueError("keys must be 1-D")
        if len(keys) > self.probe_capacity:
            raise ValueError(f"{len(keys)} keys exceed probe_capacity "
                             f"{self.probe_capacity}")
        probe = {self.key_col: keys.astype(np.int32),
                 "_req": np.arange(len(keys), dtype=np.int32)}
        return D.distribute_table(
            self.ctx, probe,
            capacity_per_shard=self._probe_cap_per_shard)

    def lookup(self, keys: np.ndarray):
        """Resolve ``keys`` -> ``(features, found)``: ``features`` maps each
        feature column to a ``(len(keys),)`` array aligned with ``keys``
        (zeros where missing) and ``found`` flags which keys had a row."""
        k = len(np.asarray(keys))
        out, d = self._lookup(self.resident, self._distribute_probe(keys))
        self.dropped += _dropped(d)
        cols = D.collect_table(self.ctx, out)
        req = cols.pop("_req")
        found = np.zeros(k, bool)
        found[req] = True
        feats = {}
        for name in self.feature_cols:
            buf = np.zeros(k, cols[name].dtype)
            buf[req] = cols[name]
            feats[name] = buf
        return feats, found

    def contains(self, keys: np.ndarray) -> np.ndarray:
        """Membership mask over ``keys`` (the semi-join path — no feature
        materialization)."""
        k = len(np.asarray(keys))
        out, d = self._contains(self.resident,
                                self._distribute_probe(keys))
        self.dropped += _dropped(d)
        cols = D.collect_table(self.ctx, out)
        found = np.zeros(k, bool)
        found[cols["_req"]] = True
        return found


# --------------------------------------------------------------------------
# The engine
# --------------------------------------------------------------------------


class ServingEngine:
    """Admission queue -> feature fetch -> slot prefill -> continuous
    decode, all through cached jitted programs (see module docstring).

    ``feature_stores`` maps a request attribute name (``"drug_id"`` /
    ``"cell_id"``) to the :class:`FeatureStore` resolving it; every store's
    ``probe_capacity`` must admit a full refill micro-batch (``slots``).
    """

    def __init__(self, cfg, params, *, policy=None, slots: int = 4,
                 prompt_capacity: int = 32, gen_capacity: int = 32,
                 queue_capacity: int = 64,
                 feature_stores: Mapping[str, FeatureStore] | None = None,
                 attn_impl: str = "xla", clock=time.perf_counter):
        if cfg.frontend != "none" or cfg.is_encdec:
            raise ValueError("ServingEngine serves decoder-only LM "
                             "configs (no frontend/encoder)")
        self.cfg = cfg
        self.params = params
        self.clock = clock
        self.n_slots = int(slots)
        self.prompt_capacity = int(prompt_capacity)
        self.gen_capacity = int(gen_capacity)
        self.decode_len = self.prompt_capacity + self.gen_capacity
        self.feature_stores = dict(feature_stores or {})
        for name, store in self.feature_stores.items():
            if store.probe_capacity < self.n_slots:
                raise ValueError(
                    f"feature store {name!r} probe_capacity "
                    f"{store.probe_capacity} < slots {self.n_slots}")

        self.metrics = ServingMetrics()
        self.queue = AdmissionQueue(queue_capacity, self.metrics)
        self.batch = SlotBatch(self.n_slots)
        self._finished: list[Request] = []

        # one static-shape cache pytree for the whole engine lifetime
        struct = M.cache_struct(cfg, self.n_slots, self.decode_len)
        self.caches = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), struct)

        prefill = M.make_slot_prefill(cfg, policy,
                                      decode_len=self.decode_len,
                                      attn_impl=attn_impl)

        def prefill_body(params, batch, length):
            logits, caches, stats = prefill(params, batch, length)
            return jnp.argmax(logits, -1).astype(jnp.int32), caches, stats

        serve = M.make_serve_step(cfg, policy, attn_impl=attn_impl)

        def decode_body(params, caches, tokens, cache_lens):
            logits, new_caches, stats = serve(params, caches, tokens,
                                              cache_lens)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            return nxt, new_caches, stats

        self._prefill = jax.jit(prefill_body)
        self._insert = jax.jit(M.write_cache_slot, donate_argnums=(0,))
        self._decode = jax.jit(decode_body, donate_argnums=(1,))

    # ------------------------------------------------------------ admission
    def submit(self, req: Request) -> bool:
        """Offer a request to the admission queue.  Returns False (and the
        rejection is counted) under backpressure — the caller may retry."""
        if not (1 <= len(req.prompt) <= self.prompt_capacity):
            raise ValueError(f"prompt length {len(req.prompt)} outside "
                             f"[1, {self.prompt_capacity}]")
        if not (1 <= req.gen_len <= self.gen_capacity):
            raise ValueError(f"gen_len {req.gen_len} outside "
                             f"[1, {self.gen_capacity}]")
        req.t_submit = self.clock()
        ok = self.queue.offer(req)
        req.status = "queued" if ok else "rejected"
        return ok

    # -------------------------------------------------------- feature fetch
    def _fetch_features(self, reqs: list[Request]) -> list[Request]:
        """One batched lookup per store for a refill micro-batch; requests
        whose key has no feature row terminate as counted
        ``feature_miss``es.  Returns the requests that resolved fully."""
        if not self.feature_stores:
            return reqs
        with trace.span("serve/feature_fetch",
                        req_ids=[r.req_id for r in reqs]):
            ok = np.ones(len(reqs), bool)
            fetched: dict[int, dict] = {i: {} for i in range(len(reqs))}
            for attr, store in self.feature_stores.items():
                keys = np.asarray([getattr(r, attr) for r in reqs])
                feats, found = store.lookup(keys)
                ok &= found
                self.metrics.inc("feature_rows", int(found.sum()))
                if store.dropped:
                    self.metrics.counters["feature_dropped"] = sum(
                        s.dropped for s in self.feature_stores.values())
                for i in range(len(reqs)):
                    if found[i]:
                        for name, col in feats.items():
                            fetched[i][name] = float(col[i])
        good = []
        for i, r in enumerate(reqs):
            if ok[i]:
                r.features = fetched[i]
                good.append(r)
            else:
                r.status = "feature_miss"
                r.t_done = self.clock()
                self.metrics.inc("feature_misses")
                self._finished.append(r)
        return good

    # --------------------------------------------------------------- refill
    def _refill(self) -> None:
        """Admit at most one queued request a step.  Every prefill stops
        all running slots; admitting every queued request at once would
        stack their prefills into one inter-token gap, so a burst of
        arrivals would set the gap tail.  One a step keeps any gap to one
        prefill and one decode."""
        if not (self.batch.free() and self.queue):
            return
        with trace.span("serve/refill"):
            r = self.queue.pop()
            r.t_admit = self.clock()        # admitted: taken off the queue
            self.metrics.observe("queue_wait", r.t_admit - r.t_submit)
            for r in self._fetch_features([r]):
                self._prefill_into(r, self.batch.free()[0])
        self.metrics.gauge("slot_occupancy", self.batch.occupancy)

    def _prefill_into(self, r: Request, slot: int) -> None:
        """Prefill ``r``'s padded prompt, write its cache into ``slot`` and
        take its first token (a host sync)."""
        prompt_len = len(r.prompt)
        with trace.span("serve/prefill", req_id=r.req_id, slot=slot,
                        prompt_len=prompt_len,
                        positions=self.prompt_capacity):
            padded = np.zeros((1, self.prompt_capacity), np.int32)
            padded[0, :prompt_len] = r.prompt
            first, one, stats = self._prefill(
                self.params, {"tokens": jnp.asarray(padded)},
                jnp.int32(prompt_len))
            self.caches = self._insert(self.caches, one, jnp.int32(slot))
            first, stats = jax.device_get((first, stats))
            first_tok = int(first[0])
        self.metrics.inc("moe_pairs_dropped", stats["moe_pairs_dropped"])
        r.t_first = self.clock()
        r.status = "active"
        r.out_tokens.append(first_tok)
        self.metrics.inc("admitted")
        self.metrics.inc("prefills")
        self.metrics.inc("tokens_generated")
        self.metrics.observe("ttft", r.t_first - r.t_submit)
        if r.gen_len == 1:              # prefill's token was the answer
            self._complete(r)
            return
        self.batch.occupy(slot, r, first_token=first_tok,
                          prompt_len=prompt_len, gen_target=r.gen_len)

    def _complete(self, r: Request) -> None:
        r.status = "done"
        r.t_done = self.clock()
        self.metrics.inc("completed")
        self.metrics.observe("latency", r.t_done - r.t_submit)
        self._finished.append(r)

    # ----------------------------------------------------------------- step
    def step(self) -> list[Request]:
        """Refill freed slots from the queue, run one decode step over the
        fixed-shape batch, and return the requests that finished."""
        with trace.span("serve/step"):
            self._refill()
            active = self.batch.active()
            if active:
                self._decode_step(len(active))
            done, self._finished = self._finished, []
        return done

    def _decode_step(self, n_active: int) -> None:
        with trace.span("serve/decode", active=n_active) as sp:
            nxt, self.caches, stats = self._decode(
                self.params, self.caches,
                jnp.asarray(self.batch.tokens),
                jnp.asarray(self.batch.cache_lens))
            with trace.span("serve/device_wait"):
                nxt, stats = jax.device_get((nxt, stats))
            if "expert_pairs" in stats:
                sp.attrs["expert_pairs"] = stats["expert_pairs"].tolist()
            self.metrics.inc("moe_pairs_dropped", stats["moe_pairs_dropped"])
            self.metrics.inc("decode_steps")
            self.metrics.inc("tokens_generated", n_active)
            finished = self.batch.advance(
                nxt, on_token=lambda s, r, t: r.out_tokens.append(t))
            for slot in finished:
                self._complete(self.batch.release(slot))
            self.metrics.gauge("slot_occupancy", self.batch.occupancy)

    @property
    def busy(self) -> bool:
        return bool(len(self.queue) or self.batch.active())

    def run_until_drained(self, max_steps: int = 1_000_000):
        """Step until the queue and every slot are empty; returns all
        requests that finished along the way."""
        out = []
        steps = 0
        while self.busy:
            out.extend(self.step())
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"engine not drained after "
                                   f"{max_steps} steps")
        return out
