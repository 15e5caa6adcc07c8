"""KV-cache management for the continuous-batching engine.

:class:`PagedKV` is the engine's KV manager: one global page pool ``[L,
1 + pages, page_tokens, Hkv, hd]`` for K and one for V, with a block table
per slot.  Its two programs are the serving path on every platform:

* the extend launch (``extend_paged``) gathers the slot's block table,
  runs ``prefill_extend`` over one prompt chunk (or the whole prompt) and
  scatters the chunk's K/V rows back into the slot's pages;
* the decode launch (``decode_paged``) runs T greedy tokens of every slot
  through ``decode_rows_paged``, which attends each slot's pages in place,
  up to its length, through the paged attention kernel
  (``kernels/paged_attention``; interpreted on the CPU, compiled on a
  TPU), then scatters the T new rows back.

Pages holding a fully-prefilled prompt prefix are content-addressed (hash
chain over page tokens) and shared across requests with the same prefix: a
prefix hit skips those prefill tokens entirely, which is what makes the
per-request command footprint (prefill doorbells, DMA payload bytes)
sublinear in shared-prefix traffic.  Pool exhaustion evicts with
``reason="kv_pages"``; the engine caps each request at ``max_seq``
(``kv_overrun``).

**Chunked prefill**: prompts longer than ``prefill_chunk`` advance one
bounded extend launch at a time (``serve.prefill_chunk`` spans),
interleaved by the engine with decode iterations so long prompts do not
stall active slots.  Chunked prefill is bit-identical to whole-prompt
prefill (masked-out future cache positions contribute exactly-zero softmax
weight; see ``models.attention``).

Page 0 of the pool is a reserved scratch page: free or still-prefilling
slots point every block-table row at it with length 0, so the decode
launch stays total and shape-stable across joins and leaves.

The engine's tokens are checked against one-shot ``Server.serve`` of the
same request, which runs the model's own ``prefill`` and ``decode_step``.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Any, Dict, List, Optional, TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.shapes import kv_geometry
from ..models.attention import gather_block_table, scatter_block_rows

if TYPE_CHECKING:                                    # pragma: no cover
    from .scheduler import RequestTicket
    from .server import Server

__all__ = ["PagedKV"]


def _require_extend(model) -> None:
    if not hasattr(model, "prefill_extend"):
        raise ValueError(
            f"the continuous-batching engine requires a model with a "
            f"prefill_extend() decode-state extension (transformer-family); "
            f"{type(model).__name__} has none")


def _hash_page(parent: bytes, tokens: np.ndarray) -> bytes:
    h = hashlib.blake2b(digest_size=16)
    h.update(parent)
    h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
    return h.digest()


class PagedKV:
    """Fixed-size pages + block tables + shared-prefix page reuse.

    Lifecycle per request: :meth:`begin` claims pages for a slot (False
    only on unrecoverable page exhaustion -> the engine evicts with
    ``reason="kv_pages"``); :meth:`prefill_step` advances the slot's
    prefill by one launch and returns the first generated token once the
    prompt is fully in cache; :meth:`reserve_decode` grows block tables
    ahead of a decode launch (returning victims when the pool cannot);
    :meth:`decode` runs the one launch over all slots; :meth:`release`
    returns the slot's pages.

    Geometry: pool ``[L, 1 + pages, page_tokens, Hkv, hd]`` (page 0 is
    scratch), block tables ``[B, max_seq // page_tokens]`` host-side.  Pages
    whose span lies entirely inside the *prompt* (decode never writes them:
    the first decode write lands at position ``prompt_len``) are registered
    under a content hash chain once prefilled, and later requests sharing
    that prefix attach to them instead of re-prefilling — always leaving at
    least the final prompt token to prefill so the first output token's
    logits exist.

    Allocation: free list first, then reclaim of the least-recently-freed
    cached (refcount-0 but registered) page.  When both are empty the
    requester loses: ``begin`` returns False / ``reserve_decode`` reports
    the slot as a victim, and the engine evicts it with
    ``reason="kv_pages"``.
    """

    name = "paged"

    def __init__(self, engine: "Server", page_tokens: int = 16,
                 pages: Optional[int] = None,
                 prefill_chunk: int = 0) -> None:
        _require_extend(engine.model)
        self.engine = engine
        self.pt = int(page_tokens)
        self.chunk = max(0, int(prefill_chunk))   # 0 = whole-prompt prefill
        self.n_blk, default_pages = kv_geometry(
            engine.max_seq, self.pt, engine.B)
        self.pages = int(pages) if pages is not None else default_pages
        if self.pages < self.n_blk:
            raise ValueError(
                f"kv_pages={self.pages} cannot hold even one full slot "
                f"({self.n_blk} pages of {self.pt} tokens)")
        cfg, model = engine.cfg, engine.model
        from ..models.layers import dtype_of
        P = 1 + self.pages                            # + scratch page 0
        shape = (cfg.n_layers, P, self.pt, cfg.n_kv_heads, cfg.hd)
        self.k_pool = jnp.zeros(shape, dtype_of(cfg))
        self.v_pool = jnp.zeros(shape, dtype_of(cfg))
        self.page_bytes = int(2 * np.prod(shape[2:]) * cfg.n_layers
                              * self.k_pool.dtype.itemsize)

        B = engine.B
        self.tables = np.zeros((B, self.n_blk), np.int32)   # 0 = scratch
        self.n_rows = np.zeros(B, np.int32)           # valid table rows
        self.lengths = np.zeros(B, np.int32)          # 0 until installed
        self.ready = np.zeros(B, bool)                # prefill complete
        self._nxt = jnp.zeros((B, 1, 1), jnp.int32)

        self._free: List[int] = list(range(P - 1, 0, -1))   # pop() -> 1,2,..
        self._ref = np.zeros(P, np.int64)
        self._key_of: Dict[int, bytes] = {}           # page -> content key
        self._page_of: Dict[bytes, int] = {}          # content key -> page
        self._cached: "OrderedDict[int, None]" = OrderedDict()  # ref==0 LRU
        self._chain: Dict[int, List[bytes]] = {}      # slot -> page keys
        self._pending: Dict[int, Dict[str, Any]] = {}

        # launch accounting (feeds loadtest --json and the chip benchmark)
        self.pages_allocated = 0                      # cumulative fresh
        self.pages_reused = 0                         # prefix-hit attaches
        self.prefix_hits = 0                          # requests that hit
        self.prefix_hit_tokens = 0
        self.pages_peak = 0
        self.decode_launches = 0
        self.prefill_launches = 0        # all prefill/extend launches
        self.prefill_chunk_launches = 0  # the subset that were chunk ticks
        self.prefill_tokens = 0          # prompt tokens actually pushed
        self.chunked_prompts = 0         # prompts that needed >1 launch
        # query-key pairs of the extend launches: every one the attention
        # scores (each query against the whole cache buffer) and those the
        # causal mask keeps (each query against itself and what precedes it)
        self.extend_scored_positions = 0
        self.extend_live_positions = 0
        # pages of the decode launches: those the attention reads and those
        # the active slots' block tables hold
        self.decode_pages_read = 0
        self.decode_pages_table = 0

        T, S = engine.T, engine.max_seq
        L, Hk, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd

        # The named scopes label the programs' ops in a profiler trace: the
        # model, the table gather, the scatter back.  The decode scatter's
        # start clamps to S - T when a finishing slot overruns, as the
        # decode body's window does.  Rows come back [L, B, T, Hkv, hd].
        def decode_paged(params, k_pool, v_pool, tables, lengths, nxt):
            with jax.named_scope("decode_pages"):
                toks, nxts, rows_k, rows_v = model.decode_rows_paged(
                    params, k_pool, v_pool, tables, lengths, nxt[:, 0], T)
                nxts = nxts[:, None]

            def body(b, pools):
                kp, vp = pools
                tbl = tables[b]
                start = jnp.minimum(lengths[b], S - T)
                kp = scatter_block_rows(kp, tbl, rows_k[:, b], start)
                vp = scatter_block_rows(vp, tbl, rows_v[:, b], start)
                return kp, vp

            with jax.named_scope("kv_scatter"):
                k_pool, v_pool = jax.lax.fori_loop(
                    0, tables.shape[0], body, (k_pool, v_pool))
            return k_pool, v_pool, toks, nxts

        # Both launches own the pools (``donate_argnums``) so that the
        # scatter writes the new rows in place: the arrays passed in are
        # deleted, and callers must rebind the outputs.
        self._decode_slots = engine.tracker.wrap(
            jax.jit(decode_paged, donate_argnums=(1, 2)), "decode_slots")

        def extend_paged(params, k_pool, v_pool, table, start, tokens):
            with jax.named_scope("kv_gather"):
                st = {"k": gather_block_table(k_pool, table),
                      "v": gather_block_table(v_pool, table),
                      "length": start}
            with jax.named_scope("extend"):
                st, logits = model.prefill_extend(params, st, tokens)
            C = tokens.shape[1]
            with jax.named_scope("kv_rows"):
                rk = jax.lax.dynamic_slice(
                    st["k"], (0, 0, start, 0, 0), (L, 1, C, Hk, hd))[:, 0]
                rv = jax.lax.dynamic_slice(
                    st["v"], (0, 0, start, 0, 0), (L, 1, C, Hk, hd))[:, 0]
            with jax.named_scope("kv_scatter"):
                k_pool = scatter_block_rows(k_pool, table, rk, start)
                v_pool = scatter_block_rows(v_pool, table, rv, start)
            return k_pool, v_pool, logits

        self._extend = engine.tracker.wrap(
            jax.jit(extend_paged, donate_argnums=(1, 2)), "prefill_extend")

    # -- page accounting ---------------------------------------------------
    @property
    def pages_in_use(self) -> int:
        return self.pages - len(self._free) - len(self._cached)

    def _take_pages(self, n: int) -> Optional[List[int]]:
        """n fresh pages, reclaiming cached prefix pages if needed."""
        got: List[int] = []
        while len(got) < n:
            if self._free:
                got.append(self._free.pop())
            elif self._cached:
                page, _ = self._cached.popitem(last=False)   # oldest
                key = self._key_of.pop(page)
                self._page_of.pop(key, None)
                got.append(page)
            else:
                self._free.extend(got)                       # rollback
                return None
        self.pages_allocated += len(got)
        self.pages_peak = max(self.pages_peak, self.pages_in_use)
        return got

    def _drop_ref(self, page: int) -> None:
        self._ref[page] -= 1
        if self._ref[page] == 0:
            if page in self._key_of:
                self._cached[page] = None            # reusable, reclaimable
            else:
                self._free.append(page)

    def _register(self, page: int, key: bytes) -> None:
        if key not in self._page_of and page not in self._key_of:
            self._page_of[key] = page
            self._key_of[page] = key

    # -- prefill -----------------------------------------------------------
    def begin(self, slot: int, tix: "RequestTicket") -> bool:
        prompt = np.asarray(tix.request.prompt, np.int32)
        plen = len(prompt)
        chain: List[bytes] = []
        key = b"kv-root"
        for p in range(plen // self.pt):             # fully-covered pages
            key = _hash_page(key, prompt[p * self.pt:(p + 1) * self.pt])
            chain.append(key)
        # shareable prefix: page span must end before the last prompt token
        # so at least one token remains to prefill (tok0 needs logits)
        n_share_max = (plen - 1) // self.pt
        shared: List[int] = []
        for p in range(min(n_share_max, len(chain))):
            pg = self._page_of.get(chain[p])
            if pg is None:
                break
            # pin BEFORE allocating: a refcount-0 shared page sits in
            # self._cached, which _take_pages reclaims under pool pressure
            # — left unpinned, the same physical page could be handed back
            # as a fresh prefill target and the prefill would clobber the
            # shared prefix content
            if self._ref[pg] == 0:
                self._cached.pop(pg, None)
            self._ref[pg] += 1
            shared.append(pg)
        n_total = -(-plen // self.pt)                # pages covering prompt
        got = self._take_pages(n_total - len(shared))
        if got is None:
            for pg in shared:                        # unpin: roll back
                self._drop_ref(pg)
            return False
        for pg in got:
            self._ref[pg] += 1
        self.tables[slot, :n_total] = shared + got
        self.tables[slot, n_total:] = 0
        self.n_rows[slot] = n_total
        self.lengths[slot] = 0
        self.ready[slot] = False
        self._chain[slot] = chain
        start = len(shared) * self.pt
        chunked = bool(self.chunk) and (plen - start) > self.chunk
        self._pending[slot] = {"tix": tix, "prompt": prompt, "pos": start,
                               "chunked": chunked}
        if chunked:
            self.chunked_prompts += 1
        if shared:
            self.prefix_hits += 1
            self.pages_reused += len(shared)
            self.prefix_hit_tokens += start
            self.engine.session.emit(
                "progress", "kv.prefix_hit", uid=tix.uid, slot=slot,
                pages=len(shared), tokens=start,
                payload_bytes=len(shared) * self.page_bytes)
        if got:
            self.engine.session.emit(
                "progress", "kv.alloc", uid=tix.uid, slot=slot,
                pages=len(got), payload_bytes=len(got) * self.page_bytes)
        return True

    def _count_extend(self, start: int, size: int) -> Dict[str, int]:
        """Count one extend launch of ``size`` prompt positions from
        ``start`` against the slot's whole cache buffer; returns the
        launch's ``scored`` and ``live`` pairs, for its span."""
        scored = size * self.n_blk * self.pt
        live = size * start + size * (size + 1) // 2
        self.extend_scored_positions += scored
        self.extend_live_positions += live
        return {"scored": scored, "live": live}

    def prefill_step(self, slot: int) -> Optional[int]:
        p = self._pending[slot]
        tix, prompt, pos = p["tix"], p["prompt"], p["pos"]
        eng = self.engine
        plen = len(prompt)
        remaining = plen - pos
        c = min(self.chunk, remaining) if p["chunked"] else remaining
        span_name = "serve.prefill_chunk" if p["chunked"] else "serve.prefill"
        table = jnp.asarray(self.tables[slot])
        pairs = self._count_extend(pos, c)
        with eng.session.span(span_name, uid=tix.uid, start=pos, size=c,
                              prompt_len=plen, **pairs):
            self.k_pool, self.v_pool, logits = self._extend(
                eng.params, self.k_pool, self.v_pool, table,
                jnp.asarray(pos, jnp.int32), jnp.asarray(prompt[None,
                                                                pos:pos + c]))
        p["pos"] = pos + c
        self.prefill_launches += 1
        if p["chunked"]:
            self.prefill_chunk_launches += 1
        self.prefill_tokens += c
        tix.n_prefill_launches += 1
        if p["pos"] < plen:
            return None
        # prompt fully in cache: register shareable pages, go decodable.
        # Pages overlapping [max_seq - T, max_seq) are excluded: a slot
        # finishing at the KV cap scatter-writes its clamped decode rows
        # there (scatter_block_rows start = min(length, S - T)), and a
        # registered page must stay immutable once other requests attach
        # to it — reachable when page_tokens < tokens_per_launch (the
        # tuner ladder offers page_tokens=4 against T=8).
        chain = self._chain[slot]
        n_reg = min(plen // self.pt,
                    (eng.max_seq - eng.T) // self.pt)
        for i in range(n_reg):
            self._register(int(self.tables[slot, i]), chain[i])
        with eng.session.span("serve.prefill.readback"):
            tok0 = int(jnp.argmax(logits[0, -1, :]))
        self.lengths[slot] = plen
        self.ready[slot] = True
        self._nxt = self._nxt.at[slot, 0, 0].set(tok0)
        del self._pending[slot]
        return tok0

    # -- decode ------------------------------------------------------------
    def reserve_decode(self, slots: List[int]) -> List[int]:
        """Grow block tables to cover the next T decode writes.

        Returns slots the pool cannot serve (after reclaiming every cached
        page) — the engine evicts those with ``reason="kv_pages"`` and
        calls again, so freed pages immediately serve the survivors.  At
        most ONE victim is returned per call: several slots crossing a
        page boundary in the same iteration must not all be evicted when
        freeing a single one would let the rest grow.
        """
        victims: List[int] = []
        for slot in slots:
            ln = int(self.lengths[slot])
            last = min(ln + self.engine.T, self.engine.max_seq) - 1
            need = last // self.pt + 1 - int(self.n_rows[slot])
            if need <= 0:
                continue
            got = self._take_pages(need)
            if got is None:
                victims.append(slot)
                return victims
            r0 = int(self.n_rows[slot])
            self.tables[slot, r0:r0 + need] = got
            self.n_rows[slot] = r0 + need
            for pg in got:
                self._ref[pg] += 1
            self.engine.session.emit(
                "progress", "kv.alloc", uid=self._uid(slot), slot=slot,
                pages=need, payload_bytes=need * self.page_bytes)
        return victims

    def _uid(self, slot: int) -> int:
        tix = self.engine._slot_tix[slot]
        return tix.uid if tix is not None else -1

    def decode(self) -> np.ndarray:
        # still-prefilling slots decode as empty scratch slots: their block
        # tables and lengths are masked so the launch never touches their
        # half-written pages.  The dispatch between the two spans stays
        # stamped with the caller's span (serve.decode_iter).
        sess = self.engine.session
        with sess.span("serve.decode.upload"):
            tables = jnp.asarray(np.where(self.ready[:, None], self.tables, 0))
            lengths = jnp.asarray(
                np.where(self.ready, self.lengths, 0).astype(np.int32))
        self.k_pool, self.v_pool, toks, self._nxt = self._decode_slots(
            self.engine.params, self.k_pool, self.v_pool, tables, lengths,
            self._nxt)
        self.decode_launches += 1
        # pages the kernel reads: the live ones below each slot's window
        # start, against every row of the active slots' tables
        active = self.lengths[self.ready]
        start = np.minimum(active, self.engine.max_seq - self.engine.T)
        self.decode_pages_table += len(active) * self.n_blk
        self.decode_pages_read += int(np.sum(-(-start // self.pt)))
        with sess.span("serve.decode.readback"):
            blocks = np.asarray(toks)                # [B, T] host sync
        self.lengths[self.ready] += self.engine.T
        return blocks

    def release(self, slot: int) -> None:
        n = int(self.n_rows[slot])
        freed = 0
        for i in range(n):
            self._drop_ref(int(self.tables[slot, i]))
            freed += 1
        if freed:
            self.engine.session.emit(
                "progress", "kv.free", uid=self._uid(slot), slot=slot,
                pages=freed, payload_bytes=freed * self.page_bytes)
        self.tables[slot, :] = 0
        self.n_rows[slot] = 0
        self.lengths[slot] = 0
        self.ready[slot] = False
        self._chain.pop(slot, None)
        self._pending.pop(slot, None)

    def stats(self) -> Dict[str, Any]:
        return {
            "backend": self.name,
            "prefill_chunk": self.chunk,
            "page_tokens": self.pt,
            "pages_total": self.pages,
            "pages_in_use": self.pages_in_use,
            "pages_peak": self.pages_peak,
            "pages_allocated": self.pages_allocated,
            "pages_reused": self.pages_reused,
            "pages_cached": len(self._cached),
            "prefix_hits": self.prefix_hits,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "decode_launches": self.decode_launches,
            "prefill_launches": self.prefill_launches,
            "prefill_chunk_launches": self.prefill_chunk_launches,
            "prefill_tokens": self.prefill_tokens,
            "prefill_payload_bytes": 4 * self.prefill_tokens,
            "chunked_prompts": self.chunked_prompts,
            "extend_scored_positions": self.extend_scored_positions,
            "extend_live_positions": self.extend_live_positions,
            "decode_pages_read": self.decode_pages_read,
            "decode_pages_table": self.decode_pages_table,
        }
