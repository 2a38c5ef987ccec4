"""Batched serving engine: admit requests, prefill, interleave decode.

A deliberately small but real scheduler: fixed decode batch slots, each
slot holding one sequence; new requests prefill into a free slot; every
engine tick decodes one token for all active slots (continuous batching).
Each slot owns its decode position (``slot_pos``), so admission can
prefill into free slots *while other slots are mid-decode*: the prefill
runs over the full batch and only the admitted rows' cache lines are
adopted (:meth:`repro.models.api.Model.prefill_into`), leaving in-flight
rows bit-stable.  The KV cache is the model's stacked cache tree — raw
mode by default, GBDI-FR compressed pages via ``serving.kv_cache`` for
attention archs (the §Perf serving variant).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import numpy.typing as npt

from repro import obs
from repro.models import mla
from repro.models.api import Model
from repro.serving import kv_cache
from repro.serving.kv_cache import KVSpec, LatentSpec, Spec


@dataclasses.dataclass
class Request:
    rid: int
    prompt: npt.NDArray[np.int32]       # (S,)
    max_new: int = 16
    out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


@functools.lru_cache(maxsize=8)
def _model_jits(model: Model) -> tuple[Callable[..., Any], Callable[..., Any]]:
    """Per-model jitted decode/prefill, shared by every Engine over that
    model: a fresh Engine must not retrace or recompile anything — serving
    respawns engines per configuration sweep cell, and the scheduler
    property suite builds hundreds.  Params are call arguments, so the
    cache pins only the (frozen, hashable) model definition."""
    return jax.jit(model.decode_step), jax.jit(model.prefill_into)


class Engine:
    def __init__(self, model: Model, params: Any, *,
                 batch_slots: int = 4, max_len: int = 256) -> None:
        self.model, self.params = model, params
        self.B, self.max_len = batch_slots, max_len
        self.cache = model.init_cache(batch_slots, max_len)
        self.slot_req: list[Request | None] = [None] * batch_slots
        self.slot_pos = np.zeros(batch_slots, np.int32)  # per-slot next write pos
        self._decode, self._prefill = _model_jits(model)

    def admit(self, reqs: list[Request]) -> int:
        """Prefill a batch of requests into free slots (same-length prompts
        share one prefill; mixed lengths run one masked prefill per
        distinct length).

        Admission works mid-generation: each prefill computes over every
        batch row, but only the admitted rows' cache lines are merged in,
        and per-slot positions mean in-flight rows keep decoding at their
        own offsets, bit-stable (regression-tested in test_substrate).

        Grouping by prompt length is a correctness requirement, not just a
        bucketing nicety: padding a shorter prompt into a longer batch
        shifts its RoPE positions and parks pad-token KV under the decode
        positions it is about to use (and desyncs sliding-window ring
        caches), so its continuation diverges from a solo admit.  One
        prefill per distinct length keeps every admit bit-identical to
        admitting that request alone (mixed-length parity test in
        test_substrate).
        """
        for i in range(self.B):  # done slots are released wholesale
            held = self.slot_req[i]
            if held is not None and held.done:
                self.release(i)
        free = [i for i, r in enumerate(self.slot_req) if r is None]
        take = reqs[: len(free)]
        if not take:
            return 0
        by_len: dict[int, list[Request]] = {}
        for r in take:
            by_len.setdefault(len(r.prompt), []).append(r)
        slot_it = iter(free)
        for S, group in sorted(by_len.items()):
            slots = [next(slot_it) for _ in group]
            toks = np.zeros((self.B, S), np.int32)
            mask = np.zeros(self.B, bool)
            for slot, r in zip(slots, group):
                toks[slot] = r.prompt
                self.slot_req[slot] = r
                mask[slot] = True
            self.cache, logits = self._prefill(
                self.params, {"tokens": jnp.asarray(toks)}, self.cache,
                jnp.asarray(mask),
            )
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
            for slot, r in zip(slots, group):
                self.slot_pos[slot] = S
                r.out.append(int(nxt[slot]))
        return len(take)

    def release(self, slot: int) -> Request | None:
        """Free one slot (the scheduler's eviction/parking hook).  The KV
        rows are left in place: they are invisible to decode (masked by the
        per-slot position) and fully overwritten by the next prefill into
        the slot."""
        r = self.slot_req[slot]
        self.slot_req[slot] = None
        self.slot_pos[slot] = 0
        return r

    def tick(self) -> bool:
        """Decode one token for every active slot. Returns any-active."""
        live = [(i, r) for i, r in enumerate(self.slot_req)
                if r is not None and not r.done]
        if not live:
            return False
        for i, r in live:
            # per-slot cache ceiling: decoding at position p writes KV row
            # p, so the last decodable position is max_len - 1 — a slot is
            # done only once slot_pos passes it (marking done at
            # max_len - 1 would silently drop the final token; regression-
            # tested against a max_new-bounded run in test_substrate).
            # Truncating frees the slot, otherwise admit() would never see
            # it released.
            if self.slot_pos[i] >= self.max_len or len(r.out) >= r.max_new:
                r.done = True
        live = [(i, r) for i, r in live if not r.done]
        if not live:
            return False
        last = np.zeros((self.B, 1), np.int32)
        for i, r in enumerate(self.slot_req):
            if r is not None and not r.done and r.out:
                last[i, 0] = r.out[-1]
        logits, self.cache = self._decode(
            self.params, {"tokens": jnp.asarray(last)}, self.cache,
            jnp.asarray(self.slot_pos),
        )
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        for i, r in live:
            self.slot_pos[i] += 1
            r.out.append(int(nxt[i]))
            if len(r.out) >= r.max_new or self.slot_pos[i] >= self.max_len:
                r.done = True
        return any(r is not None and not r.done for r in self.slot_req)


def _mla_step(spec: LatentSpec, cfg: mla.MLAConfig, params: list[mla.Params],
              caches: list[kv_cache.Cache], xs: jax.Array,
              pos: jax.Array) -> tuple[jax.Array, jax.Array, list[kv_cache.Cache]]:
    """Every layer's absorbed decode step at ``pos``: its token's latent
    row appended to its cache, then attention over that cache.  Returns
    the layers' outputs, their latent attention outputs (before ``W_UV``)
    and the caches."""
    outs, attns, new = [], [], []
    scale = mla.softmax_scale(cfg)
    for i, (p, cache) in enumerate(zip(params, caches)):
        row, q = mla.decode_in(p, cfg, xs[i], pos)
        cache = kv_cache.append_rows(spec, cache, {"c": row}, pos)
        o = kv_cache.attention_decode_latent(spec, q, cache, pos, scale)
        outs.append(mla.decode_out(p, cfg, o))
        attns.append(o)
        new.append(cache)
    return jnp.stack(outs), jnp.stack(attns), new


def _mla_prefill(spec: LatentSpec, caches: list[kv_cache.Cache], rows: jax.Array,
                 start: jax.Array) -> list[kv_cache.Cache]:
    """Each layer's latent rows ``rows[i]`` written as whole flush groups."""
    return [kv_cache.prefill_groups(spec, c, {"c": rows[i]}, start)
            for i, c in enumerate(caches)]


@jax.jit
def _adopt(tree: Any) -> Any:
    """A copy of a cache tree in new buffers."""
    return jax.tree.map(jnp.copy, tree)


class KVSession:
    """Serving-shaped driver over compressed caches.

    Owns the cache trees and the decode position; every entry point is one
    jitted dispatch.

    Over one K/V layer (``spec`` a :class:`KVSpec`, ``table`` its base
    table) this is the surface the decode-steady-state microbench
    (``benchmarks/decode_microbench.py``) and the incremental property
    tests drive: ``step(q, k, v)`` is the per-token serving cost under
    measurement — with ``spec.resident_decode`` it overlays the raw tail
    over the flush-maintained decoded region (flat in context length);
    without it every step re-decodes all pages (linear).

    Over a stack of MLA layers (``spec`` a :class:`LatentSpec`,
    ``layers=(cfg, params)`` with one parameter tree per layer, ``table``
    one base table per layer) each layer owns a latent cache:
    ``prefill(rows)`` writes every layer's context as whole flush groups,
    and ``step(x)`` runs every layer's absorbed decode step in one
    dispatch.

    Every entry point that updates the cache donates the tree it consumes,
    so XLA updates its leaves in place.  Only a tree the session alone
    holds is donated: reading ``cache`` lends the tree (the caller may
    keep it), and so does assigning one.  The next update first copies a
    lent tree into buffers of the session's own, once, under a
    ``kv.adopt`` span; ``cache_copies`` counts these copies.  The session
    keeps its own copy of the base tables it is given.
    """

    def __init__(self, spec: Spec, batch: int, table: Any, *, backend: str = "auto",
                 layers: tuple[mla.MLAConfig, list[mla.Params]] | None = None) -> None:
        self.spec, self.backend, self.layers = spec, backend, layers
        self.pos = 0
        self.cache_copies = 0
        self._lent = False
        table = jax.tree.map(jnp.copy, table)     # the updates donate it with the cache
        if layers is not None:
            assert isinstance(spec, LatentSpec)
            self._cache: Any = [kv_cache.init_compressed(spec, batch, t) for t in table]
            self._step = jax.jit(functools.partial(_mla_step, spec, layers[0]),
                                 donate_argnums=(1,))
            self._prefill = jax.jit(functools.partial(_mla_prefill, spec), donate_argnums=(0,))
            return
        assert isinstance(spec, KVSpec)
        self._cache = kv_cache.init_compressed(spec, batch, table)
        self._append = jax.jit(functools.partial(kv_cache.append, spec), donate_argnums=(0,))
        self._attend = jax.jit(functools.partial(
            kv_cache.attention_decode, spec, backend=backend))

        def prefill_body(spec: KVSpec, ks: jax.Array, vs: jax.Array,
                         cache: kv_cache.Cache, start: jax.Array) -> kv_cache.Cache:
            def body(i: jax.Array, c: kv_cache.Cache) -> kv_cache.Cache:
                k = jax.lax.dynamic_slice_in_dim(ks, i, 1, axis=1)
                v = jax.lax.dynamic_slice_in_dim(vs, i, 1, axis=1)
                return kv_cache.append(spec, c, k, v, start + i)
            out: kv_cache.Cache = jax.lax.fori_loop(0, ks.shape[1], body, cache)
            return out

        self._prefill = jax.jit(functools.partial(prefill_body, spec), donate_argnums=(2,))

    @property
    def cache(self) -> Any:
        """The cache tree: a dict over a K/V layer, a list of them over MLA
        layers.  Reading it lends it to the caller."""
        self._lent = True
        return self._cache

    @cache.setter
    def cache(self, tree: Any) -> None:
        self._cache, self._lent = tree, True

    def _own(self) -> Any:
        """The cache tree, for a call to donate: a lent tree is copied."""
        if self._lent:
            with obs.span("kv.adopt"):
                self._cache = _adopt(self._cache)
            self._lent = False
            self.cache_copies += 1
        return self._cache

    def prefill(self, *rows: jax.Array) -> None:
        """K/V: ``prefill(ks, vs)`` appends a whole (B, T, Kv, hd) context
        in one fori_loop dispatch.  MLA layers: ``prefill(rows)`` writes
        each layer's latent rows (L, B, T, R) at the current position, a
        multiple of the flush group as T is, in one dispatch that encodes
        (and decodes into the resident region) every page through
        :mod:`repro.kernels.ops`."""
        if self.layers is not None:
            (c,) = rows
            with obs.span("kv.prefill_groups"):
                self._cache = self._prefill(self._own(), c, jnp.int32(self.pos))
            self.pos += int(c.shape[2])
            return
        ks, vs = rows
        with obs.span("kv.prefill"):
            self._cache = self._prefill(ks, vs, self._own(), jnp.int32(self.pos))
        self.pos += int(ks.shape[1])

    def append(self, k: jax.Array, v: jax.Array) -> None:
        """Append one token's (B, 1, Kv, hd) K/V at the current position."""
        with obs.span("kv.append"):
            self._cache = self._append(self._own(), k, v, jnp.int32(self.pos))
        self.pos += 1

    def step(self, *inputs: jax.Array) -> Any:
        """One decode step.  K/V: ``step(q, k, v)`` appends this token's
        K/V and attends with ``q`` over everything appended so far; returns
        (B, 1, H*hd).  MLA layers: ``step(x)`` takes each layer's hidden
        state (L, B, 1, hidden) and returns each layer's attention sublayer
        output (L, B, 1, hidden) and its latent attention output
        (L, B, 1, H, kv_lora_rank), the heads' softmax-weighted latent rows
        before ``W_UV``."""
        if self.layers is not None:
            (x,) = inputs
            with obs.span("kv.step"):
                out, attn, self._cache = self._step(self.layers[1], self._own(), x,
                                                    jnp.int32(self.pos))
            self.pos += 1
            return out, attn
        q, k, v = inputs
        self.append(k, v)
        with obs.span("kv.attend"):
            out = self._attend(q, self._cache, jnp.int32(self.pos - 1))
        return out
